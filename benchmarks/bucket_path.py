"""Bucketed-gradient fast-path ablation — this repo's §4.3 analogue.

Three orthogonal knobs x the real ``reduce_gradients`` hot path on a
gradient-shaped pytree (a model parameter tree with the layer stack
unstacked into per-layer leaves — the DDP many-small-messages regime the
paper's message-rate story is about):

* ``plan``       per_step (seed: rebuild BucketPlan + CommWorld + contexts
                 inside every trace) vs persistent (``get_comm_plan`` cache
                 — the per-VCI request-cache analogue).
* ``pack``       xla (O(leaves) concat chain per bucket) vs pallas (the
                 tile/slot-aligned DMA layout: ``bucket_pack_pallas`` /
                 ``bucket_unpack_pallas`` tile-gather kernels on TPU,
                 per-slot dynamic_update_slice DMA writes off-TPU).
* ``reduction``  all_reduce vs reduce_scatter + all_gather per bucket vs
                 zero1 (ZeRO-1: reduce_scatter only — each rank's shard
                 feeds ``sharded_adamw_update`` directly and the *updated
                 params* are all-gathered in ``--zero1-wire`` dtype, bf16
                 by default, the mixed-precision deployment recipe). The
                 zero1 cells run the REAL sharded-optimizer cycle (scatter
                 -> local AdamW on m/v/master shards -> param gather), and
                 the summary reports ``zero1_wire_ratio`` against the
                 all_reduce cell — the paper-level claim that per-channel
                 payload reduction, not just channel count, sets
                 MPI+threads throughput.

Wire-byte accounting: ``link_bytes`` is parsed from the compiled HLO, but
XLA:CPU legalizes bf16 collectives by converting to f32 (bf16 is not native
on CPU), so on this emulation mesh the HLO column cannot see a narrow wire
dtype; TPU keeps bf16 collectives. ``wire_link_bytes`` therefore applies
the same ring model (all-reduce ``2(n-1)/n``, reduce-scatter / all-gather
``(n-1)/n``) to the payload dtype the program REQUESTED — the bytes a real
interconnect carries per step, param all_gather counted.

Reported per cell:

* ``ms_per_step``  — compiled steady-state wall clock per step (median).
  The headline: on the 8-device CPU mesh the concat-chain pack
  materializes a copy per operand and dominates the step, so the
  pallas/DMA layout roughly halves the step (see BENCH_bucket_path.json).
* ``trace_ms``     — re-trace cost (jit cache miss): what every retrace
  (new batch shape, knob change) pays; the persistent plan's cached
  plan/world/tables are amortized here.
* ``collectives`` / ``critical_depth`` / ``link_bytes`` — structural
  metrics from the compiled HLO (hardware-independent; reduce_scatter's
  wire-byte story transfers to the TPU target even where CPU wall clock
  does not move).

Emits ``BENCH_bucket_path.json`` via :func:`benchmarks.common.emit_json`
with a summary comparing the seed cell (xla / all_reduce / per_step) to the
fast cell (pallas / all_reduce / persistent).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import CSV, SMOKE, block, emit_json, mesh_1d, time_fn
from repro.core import TILE, get_comm_plan, plan_cache_clear, \
    plan_cache_stats, reduce_gradients
from repro.core.bucketing import ShardLayout, all_gather_shards, plan_buckets
from repro.dist.sharding import zero1_opt_specs
from repro.launch.roofline import collective_critical_depth, parse_collectives
from repro.optim.adamw import bucket_decay_masks, sharded_adamw_init, \
    sharded_adamw_update


def grads_tree(arch: str, layers: int, seed: int = 0):
    """A gradient-shaped pytree: the arch's param shapes with the layer
    stack unstacked to ``layers`` per-layer leaves (DDP message regime)."""
    from repro.configs import get_config
    from repro.models.transformer import init_params

    cfg = get_config(arch)
    struct = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    rng = np.random.default_rng(seed)
    tree = {}

    def add(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("layers"):
            for i in range(layers):  # unstack (and synthesize depth)
                tree[f"{name}/{i}"] = jnp.asarray(
                    rng.normal(size=leaf.shape[1:]) * 1e-2, jnp.float32)
        else:
            tree[name] = jnp.asarray(
                rng.normal(size=leaf.shape) * 1e-2, jnp.float32)

    jax.tree_util.tree_map_with_path(add, struct)
    return tree


def make_step(mesh, tree, *, pack: str, reduction: str, persistent: bool,
              streams: int):
    """(shard_mapped fn, example args) for one ablation cell."""
    spec_in = jax.tree_util.tree_map(lambda _: P(), tree)

    def run(tr):
        cp = get_comm_plan(tr, num_streams=streams, num_vcis=streams + 1,
                           pack=pack, token_impl="data",
                           persistent=persistent)
        rt = cp.runtime()
        red = reduce_gradients(rt, tr, cp, axis="data", mean=True,
                               pack=pack, reduction=reduction)
        return rt.barrier(red)

    f = jax.shard_map(run, mesh=mesh, in_specs=(spec_in,),
                      out_specs=spec_in, check_vma=False)
    return f, (tree,)


def make_step_zero1(mesh, tree, *, pack: str, persistent: bool, streams: int,
                    wire):
    """The full ZeRO-1 cycle as one step: grad reduce_scatter (wire dtype)
    -> sharded AdamW on the local m/v/master shards -> updated-param
    all_gather (wire dtype) on the same per-bucket contexts."""
    spec_in = jax.tree_util.tree_map(lambda _: P(), tree)
    slot_align = TILE if pack == "pallas" else None
    plan = plan_buckets(tree, streams, align=TILE, slot_align=slot_align)
    ShardLayout(plan, mesh.size)  # validate divisibility up front
    state = sharded_adamw_init(tree, plan)
    spec_state = zero1_opt_specs(mesh, state)
    masks = tuple(jnp.asarray(m) for m in bucket_decay_masks(plan))

    def run(tr, st, mask_shards):
        cp = get_comm_plan(tr, num_streams=streams, num_vcis=streams + 1,
                           pack=pack, token_impl="data",
                           persistent=persistent)
        rt = cp.runtime()
        shards, layout = reduce_gradients(
            rt, tr, cp, axis="data", mean=True, pack=pack,
            reduction="reduce_scatter", output="shards", reduce_dtype=wire)
        new_shards, new_st, _ = sharded_adamw_update(
            shards, st, lr=jnp.float32(1e-3), layout=layout,
            decay_masks=mask_shards,
            psum=lambda s: rt.all_reduce(s, cp.contexts[0], axis="data"))
        params = all_gather_shards(rt, new_shards, cp, axis="data",
                                   wire_dtype=wire)
        return rt.barrier((params, new_st))

    f = jax.shard_map(run, mesh=mesh,
                      in_specs=(spec_in, spec_state,
                                tuple(P("data") for _ in masks)),
                      out_specs=(spec_in, spec_state), check_vma=False)
    return f, (tree, state, masks)


def wire_model_bytes(tree, *, streams: int, n: int, reduction: str,
                     pack: str, wire_bytes: int = 4) -> float:
    """Ring-model per-chip wire bytes for one reduction step, using the
    REQUESTED payload dtypes (see module docstring: XLA:CPU promotes bf16
    collectives to f32, so the HLO-parsed column under-reports the dtype
    saving that TPU interconnects realize)."""
    slot_align = TILE if pack == "pallas" else None
    plan = plan_buckets(tree, streams, align=TILE, slot_align=slot_align)
    tot = plan.total_padded
    ring = (n - 1) / n
    if reduction == "all_reduce":
        return 2 * ring * tot * 4                      # f32 grad all-reduce
    if reduction == "reduce_scatter":
        return ring * tot * 4 * 2                      # f32 grad rs + grad ag
    # zero1: grad rs + PARAM ag, both in wire dtype, + the scalar norm psum
    return ring * tot * wire_bytes * 2 + 2 * ring * 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--zero1-wire", default="bfloat16",
                    help="wire dtype of the zero1 cells' grad scatter + "
                         "param gather (fp32 master shards absorb the "
                         "rounding)")
    ap.add_argument("--arch", default="olmo-1b-smoke")
    ap.add_argument("--layers", type=int, default=8,
                    help="unstacked layer count (synthetic depth)")
    ap.add_argument("--trace-reps", type=int, default=4)
    args = ap.parse_args()

    mesh = mesh_1d(args.devices)
    tree = grads_tree(args.arch, args.layers)
    n_leaves = len(jax.tree_util.tree_leaves(tree))
    n_elems = sum(l.size for l in jax.tree_util.tree_leaves(tree))
    print(f"# grads: {n_leaves} leaves, {n_elems / 1e6:.2f}M f32 elements, "
          f"{args.streams} streams, {mesh.size} devices")

    csv = CSV("bucket_path")
    rows = []
    trace_reps = 2 if SMOKE else args.trace_reps
    wire = jnp.dtype(args.zero1_wire)
    for pack in ("xla", "pallas"):
        for reduction in ("all_reduce", "reduce_scatter", "zero1"):
            for plan_mode in ("per_step", "persistent"):
                persistent = plan_mode == "persistent"
                plan_cache_clear()
                if reduction == "zero1":
                    f, fargs = make_step_zero1(
                        mesh, tree, pack=pack, persistent=persistent,
                        streams=args.streams, wire=wire)
                else:
                    f, fargs = make_step(
                        mesh, tree, pack=pack, reduction=reduction,
                        persistent=persistent, streams=args.streams)
                jf = jax.jit(f)
                hlo = jf.lower(*fargs).compile().as_text()
                jf(*fargs)  # warm
                t_jit = time_fn(lambda: block(jf(*fargs)), warmup=2, reps=10)
                # retrace cost (jit cache miss): fresh wrapper => full trace
                t_trace = time_fn(
                    lambda: jax.jit(lambda *a: f(*a)).lower(*fargs),
                    warmup=1, reps=trace_reps, min_time_s=0.0)
                d = collective_critical_depth(hlo)
                link_bytes = sum(op.link_bytes
                                 for op in parse_collectives(hlo, mesh.size))
                row = dict(pack=pack, reduction=reduction, plan=plan_mode,
                           ms_per_step=t_jit["median_s"] * 1e3,
                           ms_per_step_min=t_jit["min_s"] * 1e3,
                           trace_ms=t_trace["median_s"] * 1e3,
                           collectives=d["collective_count"],
                           critical_depth=d["critical_depth"],
                           link_bytes=link_bytes,
                           wire_link_bytes=wire_model_bytes(
                               tree, streams=args.streams, n=mesh.size,
                               reduction=reduction, pack=pack,
                               wire_bytes=wire.itemsize),
                           plan_cache=str(plan_cache_stats()))
                csv.add(**row)
                rows.append(row)
    csv.dump()

    def cell(pack, reduction, plan):
        return next(r for r in rows if r["pack"] == pack and
                    r["reduction"] == reduction and r["plan"] == plan)

    seed = cell("xla", "all_reduce", "per_step")
    fast = cell("pallas", "all_reduce", "persistent")
    ar = fast  # doubles as the f32 all_reduce baseline for the wire ratio
    z1 = cell("pallas", "zero1", "persistent")
    best = min(rows, key=lambda r: r["ms_per_step"])
    summary = {
        "seed_config": {k: seed[k] for k in ("pack", "reduction", "plan")},
        "fast_config": {k: fast[k] for k in ("pack", "reduction", "plan")},
        "seed_ms_per_step": seed["ms_per_step"],
        "fast_ms_per_step": fast["ms_per_step"],
        "step_speedup": seed["ms_per_step"] / fast["ms_per_step"],
        "seed_trace_ms": seed["trace_ms"],
        "fast_trace_ms": fast["trace_ms"],
        "trace_speedup": seed["trace_ms"] / fast["trace_ms"],
        "best_config": {k: best[k] for k in ("pack", "reduction", "plan")},
        "best_ms_per_step": best["ms_per_step"],
        # ZeRO-1 wire-byte story: grad reduce_scatter + PARAM all_gather
        # (both counted, --zero1-wire dtype) vs the f32 grad all_reduce,
        # ring model at the requested dtypes (wire_link_bytes column; the
        # HLO-parsed link_bytes shows f32 on CPU, which promotes bf16
        # collectives).
        "zero1_wire_dtype": str(wire),
        "zero1_wire_link_bytes": z1["wire_link_bytes"],
        "all_reduce_wire_link_bytes": ar["wire_link_bytes"],
        "zero1_wire_ratio": (z1["wire_link_bytes"]
                             / max(ar["wire_link_bytes"], 1)),
    }
    print(f"# summary: seed {summary['seed_ms_per_step']:.2f} ms/step -> "
          f"fast {summary['fast_ms_per_step']:.2f} ms/step "
          f"({summary['step_speedup']:.2f}x step, "
          f"{summary['trace_speedup']:.2f}x retrace)")
    print(f"# zero1 wire bytes ({summary['zero1_wire_dtype']} wire, param "
          f"all_gather counted): {z1['wire_link_bytes']/1e6:.2f} MB vs "
          f"all_reduce {ar['wire_link_bytes']/1e6:.2f} MB -> "
          f"{summary['zero1_wire_ratio']:.2f}x per step")
    emit_json("bucket_path", {"rows": rows, "summary": summary})


if __name__ == "__main__":
    main()
