"""Multi-VCI optimization ablations — paper Figs. 5, 6, 7, 8 and 19.

Starting from all optimizations ON (par_comm + VCIs + hybrid progress +
per-VCI staging + tile alignment), disable one at a time:

  all                  everything on (the paper's optimized library)
  no_per_vci_progress  progress=global: every op joins every stream
                       (6.97x in the paper)
  no_per_vci_req       staging="shared": all buckets through ONE staging
                       buffer (the request-pool lock; 39.98x in the paper)
  no_cache_align       align=1: streams share tiles (false sharing; 1.49x)
  single_vci           pool of 1: Fig 5's "multiple VCIs but no benefit"

Fig 19 (--receiver): N dominant senders, ONE polling receiver that must
iterate over all the senders' contexts (MPI-3.1 semantics) vs endpoints
(receiver addresses one pinned stream directly).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import CSV, block, mesh_1d, time_fn
from repro.core.bucketing import TILE, plan_buckets, reduce_gradients
from repro.core.collectives import CommRuntime
from repro.core.comm import CommWorld
from repro.launch.roofline import collective_critical_depth

N_STREAMS = 8


def grad_tree(key, n_devices, n_leaves=24, base=256):
    # leading dim sharded over devices => per-shard values DIFFER, so the
    # psum is a real all-reduce (replicated inputs let XLA elide it).
    ks = jax.random.split(key, n_leaves)
    return {f"w{i}": jax.random.normal(ks[i], (n_devices, base + 32 * i))
            for i in range(n_leaves)}


def build(variant: str, mesh):
    tree = grad_tree(jax.random.PRNGKey(0), mesh.size)

    progress = "global" if variant == "no_per_vci_progress" else "hybrid"
    staging = "shared" if variant == "no_per_vci_req" else "per_vci"
    align = 1 if variant == "no_cache_align" else TILE
    num_vcis = 1 if variant == "single_vci" else N_STREAMS + 1

    def step(tr):
        world = CommWorld(num_vcis=num_vcis)
        rt = CommRuntime(world, progress=progress, join_every=2 * N_STREAMS,
                         token_impl="data")
        plan = plan_buckets(tr, N_STREAMS, align=align)
        out = reduce_gradients(rt, tr, plan, axis="data", staging=staging)
        return rt.barrier(out)

    in_specs = jax.tree_util.tree_map(lambda _: P("data"), tree)
    out_specs = jax.tree_util.tree_map(lambda _: P(), tree)
    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(in_specs,),
                              out_specs=out_specs, check_vma=False))
    return f, tree


VARIANTS = ["all", "no_per_vci_progress", "no_per_vci_req", "no_cache_align",
            "single_vci"]


def bench_ablation(mesh):
    csv = CSV("progress_ablation")
    base = None
    for variant in VARIANTS:
        f, tree = build(variant, mesh)
        hlo = f.lower(tree).compile().as_text()
        f(tree)
        t = time_fn(lambda: block(f(tree)))
        d = collective_critical_depth(hlo)
        us = t["median_s"] * 1e6
        if variant == "all":
            base = us
        # `collective_count`: independent streams let XLA's combiner batch
        # the buckets into ONE fused all-reduce (count 1, depth 1) — message
        # aggregation only legal because the streams are unchained. The
        # serialized variants keep 8 chained ops (count 8, depth 8).
        csv.add(variant=variant, us_per_step=us,
                slowdown_vs_all=us / base,
                collective_count=d["collective_count"],
                critical_depth=d["critical_depth"])
    csv.dump()


def bench_receiver(mesh):
    """Fig 19: dedicated receiver iterating over sender communicators."""
    csv = CSV("dedicated_receiver")
    n = mesh.size
    perm = [(i, (i + 1) % n) for i in range(n)]
    OPS = 8

    for n_senders in (1, 2, 4, 8):
        for mode in ("communicators", "endpoints"):
            def step(x):
                world = CommWorld(num_vcis=n_senders + 1)
                if mode == "endpoints":
                    rt = CommRuntime(world, progress="per_vci",
                                     token_impl="data")
                    ctxs = [world.create(f"c{s}", vci=s % world.pool.num_vcis)
                            for s in range(n_senders)]
                else:
                    rt = CommRuntime(world, progress="hybrid",
                                     join_every=4 * n_senders,
                                     token_impl="data")
                    ctxs = [world.create(f"c{s}") for s in range(n_senders)]
                sent = []
                for s in range(n_senders):
                    v = x[s]
                    for _ in range(OPS):
                        v = rt.sendrecv(v, ctxs[s], axis="data", perm=perm)
                    sent.append(v)
                # the RECEIVER side: with communicators it must poll every
                # context in turn (chained waits); with endpoints each pair
                # is independent and the receive is the stream tail itself.
                if mode == "communicators":
                    acc = jnp.zeros_like(x[0])
                    for s in range(n_senders):
                        acc = acc + rt.wait(
                            type("R", (), {"value": sent[s],
                                           "ctx": ctxs[s]})())
                    out = acc
                else:
                    out = sum(sent)
                return rt.barrier(out)

            f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(None, None),
                                      out_specs=P(None), check_vma=False))
            x = jnp.ones((n_senders, 256), jnp.float32)
            hlo = f.lower(x).compile().as_text()
            f(x)
            t = time_fn(lambda: block(f(x)))
            d = collective_critical_depth(hlo)
            csv.add(mode=mode, senders=n_senders,
                    us_per_step=t["median_s"] * 1e6,
                    msgs_per_s=n_senders * OPS * n / t["median_s"],
                    critical_depth=d["critical_depth"])
    csv.dump()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--receiver", action="store_true")
    args = ap.parse_args()
    mesh = mesh_1d(args.devices)
    if args.receiver:
        bench_receiver(mesh)
    else:
        bench_ablation(mesh)
        bench_receiver(mesh)


if __name__ == "__main__":
    main()
