"""Message-rate microbenchmark — paper Figs. 10, 11, 12, 13, 14.

Aggregate rate at which parallel "threads" (streams) inject small messages.
Each stream issues OPS_PER_STREAM point-to-point messages (ppermute pairs,
the Isend/Irecv analogue) or RMA Puts per step. Execution modes mirror §5:

  everywhere        no thread-safety tokens at all, one stream per "core"
                    (MPI everywhere: private library state per process)
  ser_comm+orig     ONE context, global critical section (original MPICH)
  ser_comm+vcis     ONE context on the multi-VCI library (no exposed
                    parallelism -> 1 VCI; optimizations can't help)
  par_comm+orig     N contexts but a single global lock (original MPICH
                    given user-exposed parallelism)
  par_comm+vcis     N contexts -> N VCIs, hybrid progress (this paper)
  endpoints         N contexts with explicitly pinned VCIs, pure per-VCI
                    progress (the user-visible-endpoints upper bound)

Reported: million messages/s (aggregate) + the token-dependency depth
(structural serialization, hardware-independent).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import CSV, SMOKE, block, mesh_1d, time_fn
from repro.core.collectives import CommRuntime
from repro.core.comm import CommWorld

OPS_PER_STREAM = 16


def _issue(rt, v, ctx, *, collective: str, rma: bool, perm, n: int):
    """One message on ``ctx``'s stream: the p2p/RMA pair of the original
    figures, or the bucketed-reduction fast path's collectives
    (``all_reduce`` vs ``reduce_scatter``+``all_gather``) so the per-message
    software overhead of the gradient hot path is measured with the same
    stream/token machinery. Reductions are normalized by ``n`` (mean) so
    chained ops keep O(1) values — without it the 16-deep chain grows n^16
    and overflows f32 at high device counts — and so every mode (including
    the token-free ``everywhere`` baseline) runs the same program."""
    if collective == "all_reduce":
        return rt.all_reduce(v, ctx, axis="data") / n
    if collective == "reduce_scatter":
        shard = rt.reduce_scatter(v, ctx, axis="data") / n
        return rt.all_gather(shard, ctx, axis="data")
    if rma:
        return rt.put(v, ctx, axis="data", perm=perm)
    return rt.sendrecv(v, ctx, axis="data", perm=perm)


def build_step(mode: str, n_streams: int, msg_elems: int, *, rma: bool,
               mesh, no_token: bool = False, collective: str = "sendrecv"):
    """Returns a jitted step issuing n_streams x OPS_PER_STREAM messages."""
    n = mesh.size
    perm = [(i, (i + 1) % n) for i in range(n)]
    kind = "rma" if rma else "p2p"

    def step(x):  # x: per-shard (n_streams, msg_elems)
        if mode == "everywhere" or no_token:
            # private library state per core: no tokens at all
            outs = []
            for s in range(n_streams):
                v = x[s]
                for _ in range(OPS_PER_STREAM):
                    if collective == "all_reduce":
                        v = jax.lax.psum(v, "data") / n
                    elif collective == "reduce_scatter":
                        v = jax.lax.all_gather(
                            jax.lax.psum_scatter(v, "data", tiled=True) / n,
                            "data", tiled=True)
                    else:
                        v = jax.lax.ppermute(v, "data", perm)
                outs.append(v)
            return jnp.stack(outs)

        if mode == "ser_comm+orig":
            world = CommWorld(num_vcis=1)
            rt = CommRuntime(world, progress="global", token_impl="data")
            shared = world.create("c0", kind=kind)
            ctxs = [shared] * n_streams
        elif mode == "ser_comm+vcis":
            world = CommWorld(num_vcis=max(n_streams, 1))
            rt = CommRuntime(world, progress="hybrid", token_impl="data")
            shared = world.create("c0", kind=kind)
            ctxs = [shared] * n_streams
        elif mode == "par_comm+orig":
            world = CommWorld(num_vcis=1)
            rt = CommRuntime(world, progress="global", token_impl="data")
            ctxs = [world.create(f"c{s}", kind=kind) for s in range(n_streams)]
        elif mode == "par_comm+vcis":
            world = CommWorld(num_vcis=n_streams + 1)
            rt = CommRuntime(world, progress="hybrid",
                             join_every=4 * n_streams, token_impl="data")
            ctxs = [world.create(f"c{s}", kind=kind) for s in range(n_streams)]
        elif mode == "endpoints":
            world = CommWorld(num_vcis=n_streams + 1)
            rt = CommRuntime(world, progress="per_vci", token_impl="data")
            ctxs = [world.create(f"c{s}", kind=kind, vci=(s % world.pool.num_vcis))
                    for s in range(n_streams)]
        else:
            raise ValueError(mode)

        outs = []
        for s in range(n_streams):
            v = x[s]
            for _ in range(OPS_PER_STREAM):
                v = _issue(rt, v, ctxs[s], collective=collective, rma=rma,
                           perm=perm, n=n)
            outs.append(v)
        return rt.barrier(jnp.stack(outs))

    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(None, None),
                              out_specs=P(None, None), check_vma=False))
    x = jnp.ones((n_streams, msg_elems), jnp.float32)
    hlo = f.lower(x).compile().as_text()
    f(x)  # warm
    return f, x, hlo


MODES = ["everywhere", "ser_comm+orig", "ser_comm+vcis", "par_comm+orig",
         "par_comm+vcis", "endpoints"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rma", action="store_true", help="MPI_Put (Figs 13/14)")
    ap.add_argument("--no-token", action="store_true",
                    help="Fig 12: disable locking/atomics analogue")
    ap.add_argument("--collective", default="sendrecv",
                    choices=("sendrecv", "all_reduce", "reduce_scatter"),
                    help="per-stream message type: the p2p pair of the "
                         "original figures, or the gradient fast path's "
                         "all_reduce vs reduce_scatter+all_gather")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--sizes", type=int, nargs="*",
                    default=[2, 512, 8192])   # 8B .. 32KB messages
    ap.add_argument("--streams", type=int, nargs="*",
                    default=[1, 2, 4, 8, 16])
    args = ap.parse_args()

    mesh = mesh_1d(args.devices)
    if SMOKE:
        args.sizes = args.sizes[:1]
        args.streams = [s for s in args.streams if s in (1, max(args.streams))]
    if args.collective == "reduce_scatter":
        # psum_scatter needs the message length to divide the axis size
        args.sizes = [-(-m // mesh.size) * mesh.size for m in args.sizes]
    name = "message_rate" + ("_rma" if args.rma else "")
    csv = CSV(name)

    from repro.launch.roofline import collective_critical_depth

    for msg in args.sizes:
        for ns in args.streams:
            for mode in MODES:
                f, x, hlo = build_step(mode, ns, msg, rma=args.rma, mesh=mesh,
                                       no_token=args.no_token and
                                       mode == "par_comm+vcis",
                                       collective=args.collective)
                t = time_fn(lambda: block(f(x)))
                n_msgs = ns * OPS_PER_STREAM * mesh.size
                d = collective_critical_depth(hlo)
                # projected rate on a parallel network: depth is the serial
                # bottleneck, so rate scales with ops/depth (the structural
                # analogue of the paper's thread-scaling curves)
                csv.add(mode=mode, collective=args.collective, streams=ns,
                        msg_bytes=msg * 4,
                        mmsgs_per_s=n_msgs / t["median_s"] / 1e6,
                        us_per_step=t["median_s"] * 1e6,
                        critical_depth=d["critical_depth"],
                        parallelism=round(d["parallelism"], 3))
    csv.dump()


if __name__ == "__main__":
    main()
