"""Thread-safety overhead + multi-VCI setup cost — paper Figs. 2, 3, 4.

Fig 2/3: fine-grained (per-VCI tokens) vs Global (one token) in the
UNCONTENDED case (1 stream) and the crossover as streams grow. On CPU the
lock cost appears as (a) extra token ops on the critical path (measured:
us/step) and (b) the structural depth.

Fig 4: MPI_Init/Finalize time vs #VCIs — here: trace+lower+compile time of
a step using K streams (each VCI = an independent collective chain => more
HLO to build and schedule).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import CSV, block, mesh_1d, time_fn
from repro.core.collectives import CommRuntime
from repro.core.comm import CommWorld

OPS = 32


def build(mode: str, n_streams: int, mesh, msg=128):
    def step(x):
        if mode == "global":
            world = CommWorld(num_vcis=1)
            rt = CommRuntime(world, progress="global", token_impl="data")
            ctxs = [world.world] * n_streams
        else:  # fg
            world = CommWorld(num_vcis=n_streams + 1)
            rt = CommRuntime(world, progress="hybrid",
                             join_every=4 * n_streams, token_impl="data")
            ctxs = [world.create(f"c{s}") for s in range(n_streams)]
        outs = []
        for s in range(n_streams):
            v = x[s]
            for _ in range(OPS):
                v = rt.all_reduce(v, ctxs[s], axis="data")
            outs.append(v)
        return rt.barrier(jnp.stack(outs))

    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(None, None),
                                 out_specs=P(None, None), check_vma=False))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    mesh = mesh_1d(args.devices)

    csv = CSV("overhead_fg_vs_global")
    for ns in (1, 2, 4, 8, 16):
        x = jnp.ones((ns, 128), jnp.float32)
        for mode in ("global", "fg"):
            f = build(mode, ns, mesh)
            f(x)
            t = time_fn(lambda: block(f(x)))
            csv.add(mode=mode, streams=ns, us_per_step=t["median_s"] * 1e6,
                    us_per_op=t["median_s"] * 1e6 / (ns * OPS))
    csv.dump()

    # Fig 4: setup (compile) cost vs pool size
    csv2 = CSV("overhead_setup_vs_vcis")
    for nv in (1, 2, 4, 8, 16, 32):
        x = jnp.ones((nv, 128), jnp.float32)
        f = build("fg", nv, mesh)
        t0 = time.perf_counter()
        f.lower(x).compile()
        csv2.add(num_vcis=nv, compile_s=time.perf_counter() - t0)
    csv2.dump()


if __name__ == "__main__":
    main()
