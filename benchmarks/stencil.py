"""Stencil halo exchange — paper §6.1, Fig. 22 (category 1: dedicated
channels suffice).

2D 5-point stencil on a (R x C) device grid. Each device owns a sub-block;
per iteration it exchanges N/S/E/W halos with its neighbours. MPI+threads
modes map halo directions x edge-threads onto communication streams:

  funneled     MPI_THREAD_FUNNELED: ONE stream for everything
  ser_comm     all four directions on one context (MULTIPLE but unexposed)
  par_comm     the paper's odd/even communicator sets: one context per
               direction per parity -> fully independent streams
  endpoints    one pinned VCI per direction (user-visible endpoints)
  everywhere   no tokens (MPI everywhere baseline)

The paper's result: par_comm+VCIs == endpoints == everywhere. The halo
pattern is pure neighbour ppermute, so the structural depth shows exactly
whether the four directions overlap.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks.common import CSV, block, time_fn
from repro.core.collectives import CommRuntime
from repro.core.comm import CommWorld
from repro.launch.roofline import collective_critical_depth


def grid_mesh(rows, cols):
    devs = jax.devices()
    assert len(devs) >= rows * cols
    return Mesh(np.array(devs[: rows * cols]).reshape(rows, cols), ("y", "x"))


def _perms(rows, cols):
    """Neighbour permutations on the flattened (y,x) grid per direction."""
    def at(r, c):
        return r * cols + c
    north = [(at(r, c), at((r - 1) % rows, c))
             for r in range(rows) for c in range(cols)]
    south = [(at(r, c), at((r + 1) % rows, c))
             for r in range(rows) for c in range(cols)]
    west = [(at(r, c), at(r, (c - 1) % cols))
            for r in range(rows) for c in range(cols)]
    east = [(at(r, c), at(r, (c + 1) % cols))
            for r in range(rows) for c in range(cols)]
    return {"n": north, "s": south, "w": west, "e": east}


def build(mode: str, rows, cols, block_size: int, mesh):
    perms = _perms(rows, cols)
    axis = ("y", "x")

    def halo_exchange(u):
        # u: local block (B, B). Halos: first/last rows/cols.
        halos = {
            "n": u[:1, :], "s": u[-1:, :], "w": u[:, :1], "e": u[:, -1:],
        }
        if mode == "everywhere":
            recv = {d: jax.lax.ppermute(h, axis, perms[d])
                    for d, h in halos.items()}
            rt = None
        else:
            if mode == "funneled" or mode == "ser_comm":
                world = CommWorld(num_vcis=1 if mode == "funneled" else 8)
                rt = CommRuntime(world, progress="global" if mode == "funneled"
                                 else "hybrid", token_impl="data")
                ctx = world.create("halo")
                ctxs = {d: ctx for d in halos}
            elif mode == "par_comm":
                # odd/even sets: direction-parity -> independent contexts.
                # On the device grid the parity trick collapses to one
                # context per direction (threads on an edge share nothing).
                world = CommWorld(num_vcis=8)
                rt = CommRuntime(world, progress="hybrid", join_every=16,
                                 token_impl="data")
                ctxs = {d: world.create(f"halo_{d}") for d in halos}
            elif mode == "endpoints":
                world = CommWorld(num_vcis=8)
                rt = CommRuntime(world, progress="per_vci", token_impl="data")
                ctxs = {d: world.create(f"ep_{d}", vci=i + 1)
                        for i, d in enumerate(halos)}
            else:
                raise ValueError(mode)
            recv = {d: rt.sendrecv(h, ctxs[d], axis=axis, perm=perms[d])
                    for d, h in halos.items()}

        # 5-point update using the received halos
        up = jnp.concatenate([recv["s"], u[:-1, :]], axis=0)
        dn = jnp.concatenate([u[1:, :], recv["n"]], axis=0)
        lf = jnp.concatenate([recv["e"], u[:, :-1]], axis=1)
        rg = jnp.concatenate([u[:, 1:], recv["w"]], axis=1)
        out = 0.25 * (up + dn + lf + rg)
        return rt.barrier(out) if rt is not None else out

    f = jax.jit(jax.shard_map(halo_exchange, mesh=mesh,
                              in_specs=P("y", "x"), out_specs=P("y", "x"),
                              check_vma=False))
    u = jnp.ones((rows * block_size, cols * block_size), jnp.float32)
    return f, u


MODES = ["everywhere", "funneled", "ser_comm", "par_comm", "endpoints"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=16)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--cols", type=int, default=4)
    args = ap.parse_args()
    rows, cols = args.rows, args.cols
    mesh = grid_mesh(rows, cols)
    csv = CSV("stencil_halo")
    for bs in (64, 256, 1024):   # mesh sizes (local block edge)
        for mode in MODES:
            f, u = build(mode, rows, cols, bs, mesh)
            hlo = f.lower(u).compile().as_text()
            f(u)
            t = time_fn(lambda: block(f(u)))
            d = collective_critical_depth(hlo)
            csv.add(mode=mode, block=bs, us_per_iter=t["median_s"] * 1e6,
                    critical_depth=d["critical_depth"],
                    parallelism=round(d["parallelism"], 3))
    csv.dump()


if __name__ == "__main__":
    main()
