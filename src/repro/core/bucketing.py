"""Gradient bucketing onto VCI streams — the training-loop integration.

The paper's headline microbenchmark is aggregate *message rate*: many small
messages injected in parallel over independent streams. The training-loop
equivalent is gradient reduction: a pytree of many small/medium tensors that
must be summed over the ``data`` axis every step. The serialized baseline
("global critical section") funnels everything through one stream as one
chain; the VCI design partitions the tree into B buckets, assigns each bucket
a CommContext (communicator analogue), and issues B independent
reduce-scatters/all-reduces that XLA may overlap.

Paper-optimization analogues carried over:

* per-VCI request cache (§4.3, 39.98x)  →  ``staging="per_vci"``: each bucket
  packs into its own freshly-allocated flat buffer. ``staging="shared"``
  reproduces the un-optimized path: every bucket is written into ONE shared
  staging array via dynamic_update_slice, which threads a value dependency
  through all buckets and serializes them (lock on the shared request pool).
* cache-line-aligned VCIs (§4.3, 1.49x) →  ``align``: bucket payloads are
  padded to tile-aligned sizes ((8,128) f32 tiles) so no two streams' bytes
  share a tile; ``align=1`` disables it.

The FAST PATH (persistent comm plans + fused pack/unpack) adds three
orthogonal knobs, all reachable from :func:`reduce_gradients` and
``make_train_step``:

=============  =======================  =====================================
knob           values                   what changes
=============  =======================  =====================================
plan           per-step | persistent    :func:`get_comm_plan` caches the
                                        ``BucketPlan`` + ``CommWorld`` +
                                        contexts + pack index tables keyed on
                                        (treedef, shapes, knobs), so repeated
                                        ``train_step`` calls and jit retraces
                                        reuse ONE host-side plan (the §4.3
                                        per-VCI request-cache analogue).
pack           "xla" | "pallas"         "xla" packs each bucket with an
                                        O(leaves) concat chain; "pallas" lays
                                        grads into one tile-aligned arena and
                                        packs/unpacks per bucket with the
                                        ``bucket_pack_pallas`` /
                                        ``bucket_unpack_pallas`` tile-gather
                                        kernels on TPU. Off-TPU the same
                                        slot-aligned layout lowers to per-slot
                                        dynamic_update_slice DMA writes —
                                        ~2x the concat chain on the 8-device
                                        CPU mesh, where XLA:CPU materializes
                                        a copy per concat operand.
reduction      "all_reduce" |           "reduce_scatter" issues per-bucket
               "reduce_scatter"         psum_scatter + all_gather on the
                                        bucket's VCI stream — same result,
                                        half the bytes on the wire for DDP.
output         "tree" | "shards"        "tree" (default) returns the reduced
                                        pytree. "shards" (requires
                                        ``reduction="reduce_scatter"``) skips
                                        the re-gather and returns each rank's
                                        OWN slice of every reduced bucket plus
                                        the :class:`ShardLayout` describing
                                        ownership — the ZeRO-1 contract: a
                                        sharded optimizer consumes the shard
                                        directly and all-gathers the *updated
                                        params* instead (see
                                        ``repro.optim.adamw``), so gradient
                                        wire bytes are actually halved.
schedule       "post" | "overlap"       WHEN each bucket's reduce is issued.
                                        "post" (default) reduces after the
                                        full backward (one post-pass over the
                                        finished gradient tree). "overlap"
                                        wraps every bucket in a ``custom_vjp``
                                        boundary (:func:`overlap_boundaries`)
                                        so its reduce is issued on its VCI
                                        stream *inside the backward*, as soon
                                        as the bucket's cotangents exist —
                                        PyTorch-DDP bucket-ready hooks. Same
                                        wire bytes, shorter critical path:
                                        reduction becomes an event-driven
                                        consumer of the backward. Overlap
                                        plans partition leaves CONTIGUOUSLY
                                        in use order (``partition="contig"``)
                                        so buckets become ready progressively
                                        during the backward, and
                                        :func:`bucket_ready_order` gives the
                                        reverse-topological issue order.
=============  =======================  =====================================

``CommRuntime`` (and its ``ProgressEngine`` ordering tokens) is the ONLY
trace-dependent piece, so a persistent :class:`CommPlan` mints a fresh
runtime per trace via :meth:`CommPlan.runtime` while everything else is
built exactly once per (treedef, shapes, knobs).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.collectives import CommRuntime
from repro.core.comm import CommContext, CommWorld

TILE = 8 * 128  # one (8,128) f32 VREG/VMEM tile


@dataclass(frozen=True)
class LeafSlot:
    index: int            # position in the flattened tree
    shape: Tuple[int, ...]
    dtype: Any
    offset: int           # offset inside the bucket's flat buffer

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


@dataclass(frozen=True)
class Bucket:
    bid: int
    slots: Tuple[LeafSlot, ...]
    padded_size: int


@dataclass(frozen=True)
class BucketPlan:
    treedef: Any
    buckets: Tuple[Bucket, ...]
    align: int
    slot_align: Optional[int] = None  # per-slot alignment (pallas layout)

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def total_padded(self) -> int:
        return sum(b.padded_size for b in self.buckets)

    @property
    def num_leaves(self) -> int:
        return sum(len(b.slots) for b in self.buckets)


def _round_up(n: int, align: int) -> int:
    return ((n + align - 1) // align) * align


@dataclass(frozen=True)
class ShardLayout:
    """Per-rank ownership of every bucket's flat buffer (the ZeRO-1 map).

    ``reduce_scatter`` over ``axis_size`` ranks splits bucket ``b``'s
    ``padded_size`` buffer into ``axis_size`` equal contiguous shards; rank
    ``r`` receives (and owns) elements ``[r*S_b, (r+1)*S_b)`` where
    ``S_b = padded_size / axis_size``. A sharded optimizer keeps moments and
    the fp32 master copy only for the owned range and all-gathers updated
    params back into the full buffer.

    Invariants (exercised by the property tests in ``tests/test_properties``):

    * every ``padded_size`` is divisible by ``axis_size`` (enforced at
      construction), so the ``axis_size`` shard ranges tile each bucket's
      ``[0, padded_size)`` exactly — no gap, no overlap;
    * every element of every :class:`LeafSlot` therefore has exactly ONE
      owning rank (:meth:`owner_of`); a slot that straddles a shard boundary
      is split between consecutive ranks (:meth:`slot_owners` returns the
      partition pieces);
    * pack → scatter → (zero update) → all_gather → unpack is the identity
      on the original leaves.
    """

    plan: BucketPlan
    axis_size: int

    def __post_init__(self):
        if self.axis_size < 1:
            raise ValueError(f"axis_size must be >= 1, got {self.axis_size}")
        for b in self.plan.buckets:
            if b.padded_size % self.axis_size:
                raise ValueError(
                    f"bucket {b.bid} padded_size {b.padded_size} not "
                    f"divisible by axis_size {self.axis_size}; plan with "
                    f"align a multiple of the axis size (TILE covers any "
                    f"2^k mesh up to 1024)")

    @property
    def num_buckets(self) -> int:
        return self.plan.num_buckets

    @property
    def shard_sizes(self) -> Tuple[int, ...]:
        """Per-bucket local shard length (``padded_size / axis_size``)."""
        return tuple(b.padded_size // self.axis_size
                     for b in self.plan.buckets)

    def shard_bounds(self, bid: int) -> Tuple[Tuple[int, int], ...]:
        """[start, stop) of every rank's shard of bucket ``bid``."""
        s = self.plan.buckets[bid].padded_size // self.axis_size
        return tuple((r * s, (r + 1) * s) for r in range(self.axis_size))

    def owner_of(self, bid: int, offset: int) -> int:
        """The unique rank owning flat ``offset`` of bucket ``bid``."""
        b = self.plan.buckets[bid]
        if not 0 <= offset < b.padded_size:
            raise IndexError(f"offset {offset} outside bucket {bid} "
                             f"[0, {b.padded_size})")
        return offset // (b.padded_size // self.axis_size)

    def slot_owners(self, bid: int, slot: LeafSlot
                    ) -> Tuple[Tuple[int, int, int], ...]:
        """Partition of a slot's range into (rank, start, stop) pieces.

        Pieces are contiguous, cover ``[slot.offset, slot.offset+size)``
        exactly, and carry strictly increasing ranks.
        """
        s = self.plan.buckets[bid].padded_size // self.axis_size
        out, cur = [], slot.offset
        end = slot.offset + slot.size
        while cur < end:
            r = cur // s
            stop = min(end, (r + 1) * s)
            out.append((r, cur, stop))
            cur = stop
        return tuple(out)

    @property
    def total_shard_elems(self) -> int:
        """Per-rank optimizer-state footprint in elements (the 1/N claim)."""
        return sum(self.shard_sizes)


def plan_buckets(tree, num_buckets: int, *, align: int = TILE,
                 slot_align: Optional[int] = None,
                 partition: str = "size") -> BucketPlan:
    """Partition a pytree's leaves into buckets.

    ``partition="size"`` (default) is the greedy size-balanced assignment:
    best load balance across streams, but every bucket mixes leaves from all
    over the tree, so under overlap scheduling no bucket is ready until the
    backward is nearly done. ``partition="contig"`` keeps leaves CONTIGUOUS
    in flatten (= forward use) order with size-balanced split points — the
    PyTorch-DDP bucket shape: the bucket holding the last-used leaves has
    all its cotangents early in the backward and its reduce can issue while
    earlier layers are still differentiating (see
    :func:`bucket_ready_order`).

    ``slot_align`` additionally places every leaf at an aligned offset
    *inside* its bucket buffer (zero-gap padding between slots) — the
    layout contract of the Pallas pack/unpack kernels, where one
    destination tile reads from exactly one source segment.
    """
    if slot_align is not None:
        assert align % slot_align == 0, (align, slot_align)
    if partition not in ("size", "contig"):
        raise ValueError(f"unknown partition {partition!r}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves]
    num_buckets = max(1, min(num_buckets, len(leaves)))
    members: List[List[int]] = [[] for _ in range(num_buckets)]
    if partition == "size":
        order = sorted(range(len(leaves)), key=lambda i: -sizes[i])
        loads = [0] * num_buckets
        for i in order:
            b = loads.index(min(loads))
            members[b].append(i)
            loads[b] += sizes[i]
    else:  # contig: balanced prefix splits of the use-ordered leaf sequence
        total = sum(sizes)
        b, load = 0, 0
        for i in range(len(leaves)):
            left = len(leaves) - i  # leaves not yet placed (including i)
            if (b < num_buckets - 1 and members[b]
                    and (load >= total * (b + 1) / num_buckets
                         or left <= num_buckets - 1 - b)):
                b += 1
            members[b].append(i)
            load += sizes[i]
    buckets = []
    for bid, idxs in enumerate(members):
        idxs = sorted(idxs)
        slots, off = [], 0
        for i in idxs:
            if slot_align is not None:
                off = _round_up(off, slot_align)
            slots.append(LeafSlot(i, tuple(leaves[i].shape), leaves[i].dtype, off))
            off += sizes[i]
        buckets.append(Bucket(bid, tuple(slots), _round_up(max(off, 1), align)))
    return BucketPlan(treedef, tuple(buckets), align, slot_align)


def bucket_ready_order(plan: BucketPlan,
                       leaf_use_order: Optional[Sequence[int]] = None
                       ) -> Tuple[int, ...]:
    """Reverse-topological bucket order: buckets sorted by backward readiness.

    The backward pass produces cotangents in REVERSE forward-use order, so a
    bucket has all its cotangents once its *earliest-used* leaf has been
    differentiated. ``leaf_use_order`` lists leaf indices in forward use
    order (default: flatten order, which is how ``init_params`` trees are
    consumed). Buckets whose earliest leaf is used LATE in the forward are
    ready FIRST in the backward — they lead this order, so their reduces
    (and, for ZeRO-1, their param gathers) should be issued first.
    """
    if leaf_use_order is None:
        use = list(range(plan.num_leaves))
    else:
        if sorted(leaf_use_order) != list(range(plan.num_leaves)):
            raise ValueError("leaf_use_order must be a permutation of "
                             f"range({plan.num_leaves})")
        use = [0] * plan.num_leaves
        for pos, idx in enumerate(leaf_use_order):
            use[idx] = pos
    def earliest_use(b: Bucket) -> int:
        return min(use[s.index] for s in b.slots)
    return tuple(sorted(range(plan.num_buckets),
                        key=lambda bid: (-earliest_use(plan.buckets[bid]),
                                         bid)))


# ---------------------------------------------------------------------------
# pack / unpack — the XLA (concat-chain / slice) reference path
# ---------------------------------------------------------------------------

def pack_bucket(leaves: Sequence[jax.Array], bucket: Bucket,
                dtype=jnp.float32) -> jax.Array:
    """Pack a bucket's leaves into one flat, tile-aligned buffer."""
    parts = []
    cursor = 0
    for s in bucket.slots:
        assert s.offset >= cursor, "slots must be non-overlapping, in order"
        if s.offset > cursor:  # slot-aligned layout: zero-fill the gap
            parts.append(jnp.zeros((s.offset - cursor,), dtype=dtype))
            cursor = s.offset
        parts.append(leaves[s.index].astype(dtype).reshape(-1))
        cursor += s.size
    pad = bucket.padded_size - cursor
    if pad:
        parts.append(jnp.zeros((pad,), dtype=dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unpack_bucket(flat: jax.Array, bucket: Bucket) -> List[Tuple[int, jax.Array]]:
    """Inverse of pack: returns (leaf_index, value) pairs."""
    out = []
    for s in bucket.slots:
        piece = lax_slice(flat, s.offset, s.offset + s.size)
        out.append((s.index, piece.reshape(s.shape).astype(s.dtype)))
    return out


def lax_slice(x, start, stop):
    return jax.lax.slice_in_dim(x, start, stop, axis=0)


# ---------------------------------------------------------------------------
# persistent comm plans
# ---------------------------------------------------------------------------

class CommPlan:
    """Everything hoistable out of the traced step, built once and reused.

    Holds the ``BucketPlan``, the ``CommWorld`` with one pre-created
    CommContext per bucket (the VCI mapping), and — for the pallas pack
    path — the host-side tile index tables (arena layout, per-bucket pack
    tables, the global unpack table). Ordering tokens live in the
    ``ProgressEngine`` and are trace-local, so :meth:`runtime` returns a
    FRESH ``CommRuntime`` for each trace; sharing one across traces would
    leak tracers.
    """

    def __init__(self, plan: BucketPlan, *, num_vcis: int = 8,
                 vci_policy: str = "fcfs", progress: str = "hybrid",
                 join_every: int = 8, token_impl: str = "barrier",
                 schedule: str = "post"):
        if schedule not in ("post", "overlap"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self.plan = plan
        self.world = CommWorld(num_vcis=num_vcis, policy=vci_policy)
        self.contexts: Tuple[CommContext, ...] = tuple(
            self.world.create(f"bucket{b.bid}", kind="p2p")
            for b in plan.buckets)
        self.progress = progress
        self.join_every = join_every
        self.token_impl = token_impl
        self.schedule = schedule
        self._tables = None
        self._ready_order: Optional[Tuple[int, ...]] = None

    @property
    def ready_order(self) -> Tuple[int, ...]:
        """Bucket issue order for overlap scheduling (backward readiness)."""
        if self._ready_order is None:
            self._ready_order = bucket_ready_order(self.plan)
        return self._ready_order

    def runtime(self) -> CommRuntime:
        """A fresh per-trace runtime bound to the cached world/contexts."""
        return CommRuntime(self.world, progress=self.progress,
                           join_every=self.join_every,
                           token_impl=self.token_impl)

    # -- pallas tile tables (lazy, computed once) -----------------------
    @property
    def tables(self):
        """(tile, arena_offsets, arena_size, pack_tables, unpack_table).

        ``pack_tables[b]`` maps bucket ``b``'s destination tiles to arena
        source tiles; ``unpack_table`` maps arena tiles back into the
        CONCATENATION of all reduced bucket buffers (bucket base offsets
        are the running sum of padded sizes).
        """
        if self._tables is None:
            from repro.kernels.bucket_pack import arena_layout, build_tile_tables

            plan = self.plan
            tile = plan.slot_align
            assert tile is not None, (
                "pallas pack path needs a slot-aligned plan "
                "(plan_buckets(..., slot_align=TILE))")
            n_leaves = plan.num_leaves
            sizes = [0] * n_leaves
            for b in plan.buckets:
                for s in b.slots:
                    sizes[s.index] = s.size
            arena_offs, arena_size = arena_layout(sizes, tile)
            pack_tables = []
            for b in plan.buckets:
                blk, val = build_tile_tables(
                    [arena_offs[s.index] for s in b.slots],
                    [s.offset for s in b.slots],
                    [s.size for s in b.slots], b.padded_size, tile)
                pack_tables.append((blk, val))
            bases = np.cumsum([0] + [b.padded_size for b in plan.buckets])
            src, dst, szs = [], [], []
            for bi, b in enumerate(plan.buckets):
                for s in b.slots:
                    src.append(int(bases[bi]) + s.offset)
                    dst.append(int(arena_offs[s.index]))
                    szs.append(s.size)
            unpack_table = build_tile_tables(src, dst, szs, arena_size, tile)
            self._tables = (tile, arena_offs, arena_size,
                            tuple(pack_tables), unpack_table)
        return self._tables


_PLAN_CACHE: Dict[Any, CommPlan] = {}
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0, "builds": 0}


def comm_plan_key(grads, *, num_streams: int, align: int,
                  slot_align: Optional[int], num_vcis: int, vci_policy: str,
                  progress: str, join_every: int, token_impl: str,
                  schedule: str = "post"):
    """Hashable cache key: tree structure + leaf shapes/dtypes + knobs."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    shapes = tuple((tuple(l.shape), jnp.dtype(l.dtype).name) for l in leaves)
    return (treedef, shapes, num_streams, align, slot_align, num_vcis,
            vci_policy, progress, join_every, token_impl, schedule)


def get_comm_plan(grads, *, num_streams: int = 8, align: int = TILE,
                  pack: str = "xla", num_vcis: int = 8,
                  vci_policy: str = "fcfs", progress: str = "hybrid",
                  join_every: int = 8, token_impl: str = "barrier",
                  schedule: str = "post",
                  persistent: bool = True) -> CommPlan:
    """Build (or fetch) the CommPlan for a gradient pytree.

    ``persistent=True`` (the fast path) caches on (treedef, shapes, knobs):
    repeated eager ``train_step`` calls and jit retraces pay the Python
    plan/world construction exactly once. ``persistent=False`` rebuilds
    from scratch every call — the seed behaviour, kept for the ablation.

    ``schedule="overlap"`` keys a separate plan whose buckets are
    CONTIGUOUS in leaf-use order (``partition="contig"``) so they become
    ready progressively during the backward — the layout
    :func:`overlap_boundaries` consumes.
    """
    slot_align = align if pack == "pallas" else None
    key = comm_plan_key(grads, num_streams=num_streams, align=align,
                        slot_align=slot_align, num_vcis=num_vcis,
                        vci_policy=vci_policy, progress=progress,
                        join_every=join_every, token_impl=token_impl,
                        schedule=schedule)
    if persistent:
        cached = _PLAN_CACHE.get(key)
        if cached is not None:
            _PLAN_CACHE_STATS["hits"] += 1
            return cached
        _PLAN_CACHE_STATS["misses"] += 1
    partition = "contig" if schedule == "overlap" else "size"
    plan = plan_buckets(grads, num_streams, align=align,
                        slot_align=slot_align, partition=partition)
    cp = CommPlan(plan, num_vcis=num_vcis, vci_policy=vci_policy,
                  progress=progress, join_every=join_every,
                  token_impl=token_impl, schedule=schedule)
    _PLAN_CACHE_STATS["builds"] += 1
    if persistent:
        _PLAN_CACHE[key] = cp
    return cp


def plan_cache_stats() -> Dict[str, int]:
    return dict(_PLAN_CACHE_STATS, size=len(_PLAN_CACHE))


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    for k in _PLAN_CACHE_STATS:
        _PLAN_CACHE_STATS[k] = 0


# ---------------------------------------------------------------------------
# the bucketed reduction itself
# ---------------------------------------------------------------------------

def _pack_bucket_dma(leaves, bucket: Bucket, dtype) -> jax.Array:
    """Non-TPU lowering of the pallas pack: one dynamic_update_slice per
    slot into the zero-initialized staging buffer — the XLA analogue of the
    kernel's per-segment DMA writes. Identical output to the kernel (and to
    ``pack_bucket``); measured ~3x faster than the concat chain on the
    8-device CPU mesh, where XLA:CPU executes each DUS as an in-place
    contiguous memcpy but pays a full materialization per concat operand."""
    buf = jnp.zeros((bucket.padded_size,), dtype)
    for s in bucket.slots:
        buf = jax.lax.dynamic_update_slice(
            buf, leaves[s.index].astype(dtype).reshape(-1), (s.offset,))
    return buf


def reduce_gradients(
    rt: CommRuntime,
    grads,
    plan: Union[BucketPlan, CommPlan],
    *,
    axis="data",
    mean: bool = True,
    staging: str = "per_vci",
    reduce_dtype=jnp.float32,
    contexts=None,
    pack: str = "xla",
    reduction: str = "all_reduce",
    output: str = "tree",
):
    """All-reduce a gradient pytree over ``axis`` on VCI streams.

    One CommContext per bucket (created here unless supplied or cached on a
    :class:`CommPlan`). Knobs (see module docstring): ``staging`` shared vs
    per-VCI buffers, ``pack`` xla-concat vs pallas tile-gather, ``reduction``
    all_reduce vs reduce_scatter+all_gather. The reduce-scatter variant
    falls back to all_reduce for any bucket whose padded size does not
    divide the axis size (never with tile alignment on 2^k-device meshes).

    ``output="shards"`` (requires ``reduction="reduce_scatter"``) stops after
    the scatter: returns ``(shards, layout)`` where ``shards[b]`` is this
    rank's float32 slice of reduced bucket ``b`` (mean already applied when
    ``mean=True``) and ``layout`` is the :class:`ShardLayout`. Every bucket
    must then divide the axis size — there is no all_reduce fallback, by
    construction the caller is a sharded optimizer that owns exactly 1/N of
    each bucket. ``reduce_dtype`` is the WIRE dtype of the scatter (bf16
    wire + fp32 shards is the mixed-precision ZeRO recipe).
    """
    if pack not in ("xla", "pallas"):
        raise ValueError(f"unknown pack impl {pack!r}")
    if reduction not in ("all_reduce", "reduce_scatter"):
        raise ValueError(f"unknown reduction {reduction!r}")
    if output not in ("tree", "shards"):
        raise ValueError(f"unknown output {output!r}")
    if output == "shards" and reduction != "reduce_scatter":
        raise ValueError("output='shards' requires reduction='reduce_scatter'")

    comm_plan = plan if isinstance(plan, CommPlan) else None
    bplan: BucketPlan = comm_plan.plan if comm_plan is not None else plan
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if contexts is None:
        if comm_plan is not None:
            contexts = comm_plan.contexts
        else:
            contexts = [rt.world.create(kind="p2p") for _ in bplan.buckets]

    # ---- pack --------------------------------------------------------------
    on_tpu = jax.default_backend() == "tpu"
    if pack == "pallas" and on_tpu:
        from repro.kernels.bucket_pack import (arena_from_leaves,
                                               bucket_pack_pallas)

        if comm_plan is not None:
            tile, arena_offs, arena_size, pack_tables, unpack_table = \
                comm_plan.tables
        else:
            tile, arena_offs, arena_size, pack_tables, unpack_table = \
                CommPlan(bplan, num_vcis=1).tables
        arena, _ = arena_from_leaves(leaves, tile=tile, dtype=reduce_dtype)
        assert arena.shape[0] == arena_size, (arena.shape, arena_size)
        packed = [bucket_pack_pallas(arena, jnp.asarray(t[0]),
                                     jnp.asarray(t[1]), b.padded_size,
                                     tile=tile)
                  for t, b in zip(pack_tables, bplan.buckets)]
    elif pack == "pallas":
        # Non-TPU lowering of the same layout contract: per-slot DMA writes
        # (dynamic_update_slice) instead of the tile-gather kernel.
        packed = [_pack_bucket_dma(leaves, b, reduce_dtype)
                  for b in bplan.buckets]
    else:
        packed = [pack_bucket(leaves, b, dtype=reduce_dtype)
                  for b in bplan.buckets]

    if staging == "shared":
        # One staging array; each bucket is inserted then re-extracted,
        # threading a value dependency through every stream (serialized).
        stage = jnp.zeros((bplan.total_padded,), dtype=reduce_dtype)
        offs = np.cumsum([0] + [b.padded_size for b in bplan.buckets])
        for i, p in enumerate(packed):
            stage = jax.lax.dynamic_update_slice(stage, p, (int(offs[i]),))
        packed = [jax.lax.dynamic_slice(stage, (int(offs[i]),),
                                        (bplan.buckets[i].padded_size,))
                  for i in range(len(packed))]

    # ---- reduce ------------------------------------------------------------
    n = _axis_size(axis)

    if output == "shards":
        layout = ShardLayout(bplan, n)  # raises on indivisible buckets
        shards = []
        for p, ctx in zip(packed, contexts):
            shard = rt.reduce_scatter(p, ctx, axis=axis).astype(jnp.float32)
            shards.append(shard / n if mean else shard)
        return shards, layout

    reduced = [_reduce_flat(rt, ctx, p, axis=axis, n=n, mean=mean,
                            reduction=reduction, padded=b.padded_size)
               for p, ctx, b in zip(packed, contexts, bplan.buckets)]

    # ---- unpack ------------------------------------------------------------
    out_leaves: List[Optional[jax.Array]] = [None] * len(leaves)
    if pack == "pallas" and on_tpu:
        from repro.kernels.bucket_pack import bucket_unpack_pallas

        reduced_all = (jnp.concatenate(reduced) if len(reduced) > 1
                       else reduced[0])
        out_arena = bucket_unpack_pallas(
            reduced_all, jnp.asarray(unpack_table[0]),
            jnp.asarray(unpack_table[1]), arena_size, tile=tile)
        sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves]
        for i, leaf in enumerate(leaves):
            off = int(arena_offs[i])
            piece = lax_slice(out_arena, off, off + sizes[i])
            out_leaves[i] = piece.reshape(leaf.shape).astype(leaf.dtype)
    else:
        # slice-per-slot unpack (a contiguous read per leaf; already the
        # fastest form on CPU — see BENCH_bucket_path.json)
        for flat, b in zip(reduced, bplan.buckets):
            for idx, val in unpack_bucket(flat, b):
                out_leaves[idx] = val
    assert all(v is not None for v in out_leaves)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def _axis_size(axis) -> int:
    return jax.lax.axis_size(tuple(axis) if isinstance(axis, list) else axis)


# ---------------------------------------------------------------------------
# bucket-ready overlap scheduling (schedule="overlap")
# ---------------------------------------------------------------------------

def _reduce_flat(rt: CommRuntime, ctx, flat, *, axis, n: int, mean: bool,
                 reduction: str, padded: int):
    """One bucket buffer's reduction: reduce_scatter + all_gather when the
    bucket divides the axis, else all_reduce. SHARED by the post-pass
    (``reduce_gradients``) and the overlap boundaries, so the two schedules
    stay op-for-op identical by construction."""
    if reduction == "reduce_scatter" and padded % n == 0:
        shard = rt.reduce_scatter(flat, ctx, axis=axis)
        if mean:
            shard = shard / n
        return rt.all_gather(shard, ctx, axis=axis)
    r = rt.all_reduce(flat, ctx, axis=axis)
    return r / n if mean else r


def _bucket_boundary(cp: CommPlan, bucket: Bucket, ctx, *, axis, n: int,
                     mean: bool, pack: str, reduction: str, reduce_dtype,
                     accum_steps: int, shards_mode: bool):
    """A ``custom_vjp`` identity over one bucket's leaves whose BACKWARD
    issues that bucket's reduction on its VCI stream.

    Forward: ``boundary(leaves, tap, carry) -> leaves`` (identity; ``tap``
    and ``carry`` do not touch the forward values). Backward: the incoming
    cotangents ARE the bucket's gradients, available the moment AD reaches
    this bucket's leaves — reverse-topologically *before* earlier layers
    finish differentiating — so the pack + reduce emitted here carries a
    data dependency on this bucket alone and XLA may run it concurrently
    with the rest of the backward. Each boundary mints a FRESH runtime:
    per-bucket (per-stream) ordering is exactly what makes early issue
    legal (MPIX-stream semantics); cross-stream joins would re-serialize
    the very overlap being created.

    ``carry`` (microbatch accumulation) holds the mean-scaled gradient sum
    of all earlier microbatches; the backward folds the final microbatch in
    with the same ``carry + ct/accum_steps`` arithmetic the post-schedule
    scan uses, so numerics match bit-for-bit. ``tap`` is only used in
    ``shards_mode``: the reduce_scatter shard leaves the backward as the
    tap's "gradient" (the ZeRO-1 side channel — cotangent shapes must match
    their primals, so the 1/N shard cannot ride out on the params).

    ``pack="pallas"`` here means the SLOT-ALIGNED LAYOUT with per-slot DUS
    writes on every backend — the boundary never dispatches the fused
    ``bucket_pack_pallas`` tile-gather kernel, even on TPU, because the
    kernel's tables index one global arena spanning ALL leaves while a
    boundary sees only its own bucket's cotangents. Per-bucket tile tables
    would lift this (ROADMAP); until then overlap-on-TPU pays the DUS
    lowering where the post schedule pays the fused kernel.
    """
    pack_dma = pack == "pallas"

    def _total(carry, cts):
        if carry is None:
            return list(cts)
        return [(c + ct.astype(jnp.float32) / accum_steps).astype(s.dtype)
                for c, ct, s in zip(carry, cts, bucket.slots)]

    def _pack(vals):
        full: List[Optional[jax.Array]] = \
            [None] * (max(s.index for s in bucket.slots) + 1)
        for s, v in zip(bucket.slots, vals):
            full[s.index] = v
        if pack_dma:
            return _pack_bucket_dma(full, bucket, reduce_dtype)
        return pack_bucket(full, bucket, dtype=reduce_dtype)

    @jax.custom_vjp
    def boundary(leaves, tap, carry):
        return leaves

    def fwd(leaves, tap, carry):
        return leaves, carry

    def bwd(carry, cts):
        rt = cp.runtime()
        flat = _pack(_total(carry, cts))
        carry_ct = None if carry is None else \
            tuple(jnp.zeros_like(c) for c in carry)
        if shards_mode:
            shard = rt.reduce_scatter(flat, ctx, axis=axis) \
                .astype(jnp.float32)
            if mean:
                shard = shard / n
            zero_cts = tuple(jnp.zeros(s.shape, s.dtype)
                             for s in bucket.slots)
            return zero_cts, shard, carry_ct
        reduced = _reduce_flat(rt, ctx, flat, axis=axis, n=n, mean=mean,
                               reduction=reduction, padded=bucket.padded_size)
        by_index = dict(unpack_bucket(reduced, bucket))
        return (tuple(by_index[s.index] for s in bucket.slots), None,
                carry_ct)

    boundary.defvjp(fwd, bwd)
    return boundary


def overlap_boundaries(
    cp: CommPlan,
    params,
    *,
    axis,
    taps: Optional[Sequence[jax.Array]] = None,
    carry=None,
    accum_steps: int = 1,
    mean: bool = True,
    pack: str = "xla",
    reduction: str = "all_reduce",
    reduce_dtype=jnp.float32,
):
    """Wrap ``params`` so every bucket's gradient reduce is issued INSIDE
    the backward, on the bucket's dedicated VCI stream, as soon as its
    cotangents exist (bucket-ready hooks, PyTorch-DDP style).

    Returns the wrapped parameter tree (forward values are unchanged).
    Differentiating a loss of the wrapped tree w.r.t. ``params`` yields the
    *already-reduced* mean gradients — ``reduce_gradients`` must NOT run
    again. With ``taps`` (ZeRO-1 mode: one zero-initialized f32 array of
    shard size per bucket, see :class:`ShardLayout`), the params' gradients
    are zeros and each tap's gradient is instead this rank's mean-reduced
    ``reduce_scatter`` shard of its bucket (``reduce_dtype`` = wire dtype),
    exactly what ``reduce_gradients(..., output="shards")`` returns post-hoc.

    ``carry`` threads microbatch accumulation through the boundary: pass
    the mean-scaled gradient sum of all *earlier* microbatches (a tree like
    ``params``) plus ``accum_steps``, and differentiate only the LAST
    microbatch's loss — the backward folds the carry in before reducing, so
    one set of reduces per step, not per microbatch.
    """
    bplan = cp.plan
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if treedef != bplan.treedef:
        raise ValueError("params tree does not match the CommPlan's tree")
    shards_mode = taps is not None
    if shards_mode:
        if len(taps) != bplan.num_buckets:
            raise ValueError(f"need one tap per bucket "
                             f"({bplan.num_buckets}), got {len(taps)}")
    carry_leaves = None
    if carry is not None:
        carry_leaves = treedef.flatten_up_to(carry)
    n = _axis_size(axis)
    if shards_mode:
        ShardLayout(bplan, n)  # raises on indivisible buckets
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    for b in bplan.buckets:
        boundary = _bucket_boundary(
            cp, b, cp.contexts[b.bid], axis=axis, n=n, mean=mean, pack=pack,
            reduction=reduction, reduce_dtype=reduce_dtype,
            accum_steps=accum_steps, shards_mode=shards_mode)
        b_leaves = tuple(leaves[s.index] for s in b.slots)
        b_carry = None if carry_leaves is None else \
            tuple(carry_leaves[s.index] for s in b.slots)
        tap = taps[b.bid] if shards_mode else None
        wrapped = boundary(b_leaves, tap, b_carry)
        for s, w in zip(b.slots, wrapped):
            out[s.index] = w
    assert all(v is not None for v in out)
    return jax.tree_util.tree_unflatten(treedef, out)


def all_gather_shards(rt: CommRuntime, shards: Sequence[jax.Array],
                      plan: Union[BucketPlan, CommPlan], *, axis,
                      contexts=None, wire_dtype=None,
                      order: Optional[Sequence[int]] = None):
    """Rebuild the full pytree from per-rank bucket shards (ZeRO-1 step 3).

    The inverse of ``reduce_gradients(..., output="shards")`` composed with
    ``unpack``: each bucket's local shard is all-gathered on the SAME
    CommContext/VCI its reduce_scatter used (when ``plan`` is the CommPlan),
    re-assembling the ``padded_size`` buffer, which is then unpacked into
    leaves (cast to each LeafSlot's dtype). ``wire_dtype`` sets the gather
    payload dtype — param-dtype wire (e.g. bf16) halves the gather bytes
    and is lossless when every leaf shares that dtype. ``order`` sets the
    per-bucket ISSUE order (default: bucket id); the overlap trainer passes
    ``CommPlan.ready_order`` so first-ready buckets' gathers chain first on
    their streams and pipeline behind later buckets' reduces.
    """
    comm_plan = plan if isinstance(plan, CommPlan) else None
    bplan: BucketPlan = comm_plan.plan if comm_plan is not None else plan
    if contexts is None:
        if comm_plan is not None:
            contexts = comm_plan.contexts
        else:
            contexts = [rt.world.create(kind="p2p") for _ in bplan.buckets]
    if order is None:
        order = range(bplan.num_buckets)
    out_leaves: List[Optional[jax.Array]] = [None] * bplan.num_leaves
    for bid in order:
        shard, ctx, b = shards[bid], contexts[bid], bplan.buckets[bid]
        if wire_dtype is not None:
            shard = shard.astype(wire_dtype)
        flat = rt.all_gather(shard, ctx, axis=axis)
        for idx, val in unpack_bucket(flat, b):
            out_leaves[idx] = val
    assert all(v is not None for v in out_leaves)
    return jax.tree_util.tree_unflatten(bplan.treedef, out_leaves)
