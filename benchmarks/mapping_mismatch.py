"""VCI-mapping mismatch — paper Fig. 17.

16 streams of user-exposed parallelism against pool sizes 1..16: with fewer
VCIs than streams, FCFS assignment collides contexts onto the fallback VCI
and serializes them even though the USER did everything right. The
``hinted`` policy (the paper's §5.2 suggestion) and explicit endpoint
pinning are shown as the remedies.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import CSV, block, mesh_1d, time_fn
from repro.core.collectives import CommRuntime
from repro.core.comm import CommWorld
from repro.launch.roofline import collective_critical_depth

N_STREAMS = 16
OPS = 8


def build(pool_size: int, mesh, *, policy="fcfs", pin=False):
    n = mesh.size
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(x):
        world = CommWorld(num_vcis=pool_size, policy=policy)
        rt = CommRuntime(world, progress="hybrid", join_every=4 * N_STREAMS,
                         token_impl="data")
        ctxs = []
        for s in range(N_STREAMS):
            if pin:
                ctxs.append(world.create(f"c{s}", vci=s % pool_size))
            else:
                hint = "dedicated" if policy == "hinted" else None
                ctxs.append(world.create(f"c{s}", hint=hint))
        outs = []
        for s in range(N_STREAMS):
            v = x[s]
            for _ in range(OPS):
                v = rt.sendrecv(v, ctxs[s], axis="data", perm=perm)
            outs.append(v)
        return rt.barrier(jnp.stack(outs))

    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(None, None),
                              out_specs=P(None, None), check_vma=False))
    return f


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    mesh = mesh_1d(args.devices)
    csv = CSV("mapping_mismatch")
    x = jnp.ones((N_STREAMS, 64), jnp.float32)
    for pool in (1, 2, 4, 8, 16, 17):
        for policy, pin in (("fcfs", False), ("hinted", False),
                            ("fcfs", True)):
            label = "endpoints(pinned)" if pin else policy
            f = build(pool, mesh, policy=policy, pin=pin)
            hlo = f.lower(x).compile().as_text()
            f(x)
            t = time_fn(lambda: block(f(x)))
            d = collective_critical_depth(hlo)
            csv.add(pool_size=pool, policy=label,
                    us_per_step=t["median_s"] * 1e6,
                    msgs_per_s=N_STREAMS * OPS * mesh.size / t["median_s"],
                    critical_depth=d["critical_depth"],
                    parallelism=round(d["parallelism"], 3))
    csv.dump()


if __name__ == "__main__":
    main()
