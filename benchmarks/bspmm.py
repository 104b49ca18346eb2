"""BSPMM get-compute-update — paper §6.3, Fig. 27 (category 3: MPI
semantics limit exposable parallelism).

NWChem's tensor-contraction pattern: each worker Gets A/B tiles (its own
window — fine), multiplies, then ACCUMULATES into the shared C window.
MPI-3.1 forces every thread onto ONE window for MPI_Accumulate (atomicity
across windows is undefined) and orders same-location accumulates, so the
accumulate stream serializes. The three ways out, all measured:

  mpi31_ordered     one C window, ordered accumulates (the constraint)
  mpi31_relaxed     accumulate_ordering="none" (the paper's §6.3 hint)
  endpoints         per-thread endpoints INSIDE one window (the proposal)
  everywhere        MPI-everywhere baseline (no tokens)

Paper's finding: ordered accumulates serialize; the hint restores endpoint
parity — extensions to the standard are not required.
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

N_WORKERS = 8


def build(mode: str, tile: int, mesh):
    n = mesh.size
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(a_tiles, b_tiles):
        if mode == "everywhere":
            outs = []
            for w in range(N_WORKERS):
                a = jax.lax.ppermute(a_tiles[w], "data", perm)
                b = jax.lax.ppermute(b_tiles[w], "data", perm)
                c = a @ b
                outs.append(jax.lax.psum(c, "data"))
            return jnp.stack(outs)

        world = CommWorld(num_vcis=N_WORKERS + 1)
        if mode == "endpoints":
            rt = CommRuntime(world, progress="per_vci", token_impl="data")
            getw = [world.create(f"g{w}", kind="rma", vci=w + 1)
                    for w in range(N_WORKERS)]
            # endpoints: each thread its own stream INSIDE the C window
            accw = [world.create(f"acc{w}", kind="rma", vci=w + 1,
                                 accumulate_ordering="none")
                    for w in range(N_WORKERS)]
        else:
            rt = CommRuntime(world, progress="hybrid",
                             join_every=4 * N_WORKERS, token_impl="data")
            getw = [world.create(f"g{w}", kind="rma")
                    for w in range(N_WORKERS)]
            ordering = "none" if mode == "mpi31_relaxed" else "rar"
            cwin = world.create("C", kind="rma",
                                accumulate_ordering=ordering)
            accw = [cwin] * N_WORKERS
        outs = []
        for w in range(N_WORKERS):
            a = rt.get(a_tiles[w], getw[w], axis="data", perm=perm)
            b = rt.get(b_tiles[w], getw[w], axis="data", perm=perm)
            c = a @ b
            outs.append(rt.accumulate(c, accw[w], axis="data"))
        return rt.barrier(jnp.stack(outs))

    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(P(None, None, None),) * 2,
                              out_specs=P(None, None, None),
                              check_vma=False))
    a = jnp.ones((N_WORKERS, tile, tile), jnp.float32)
    return f, a


MODES = ["everywhere", "mpi31_ordered", "mpi31_relaxed", "endpoints"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    mesh = mesh_1d(args.devices)
    csv = CSV("bspmm")
    for tile in (32, 128, 256):
        for mode in MODES:
            f, a = build(mode, tile, mesh)
            hlo = f.lower(a, a).compile().as_text()
            f(a, a)
            t = time_fn(lambda: block(f(a, a)))
            d = collective_critical_depth(hlo)
            csv.add(mode=mode, tile=tile,
                    us_per_workunit=t["median_s"] * 1e6 / N_WORKERS,
                    critical_depth=d["critical_depth"],
                    parallelism=round(d["parallelism"], 3))
    csv.dump()


if __name__ == "__main__":
    main()
