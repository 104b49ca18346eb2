"""EBMS energy-band remote fetch — paper §6.2, Figs. 24/25 (category 2:
shared progress).

Each worker (stream) fetches a band shard from a remote node: MPI_Get +
MPI_Win_flush. Modes: everywhere / par_win+vcis / endpoints, one window per
stream (the paper's Fig. 23 parallelism).

The paper's OPA cluster collapses here because software-emulated RMA needs
TARGET-side progress and independent VCIs oppose shared progress. TPU ICI
(like Mellanox IB in the paper) progresses RMA in hardware — collectives
complete without a target-side poll — so the interesting measurable is the
FLUSH dependency structure: per-VCI flush orders on ONE stream (cheap);
global-progress flush joins every stream (the paper's correctness fallback,
expensive). Both are reported.
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


def build(mode: str, band_elems: int, mesh):
    n = mesh.size
    # each worker fetches from the next node (the band server)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(bands):
        if mode == "everywhere":
            outs = [jax.lax.ppermute(bands[w], "data", perm)
                    for w in range(N_WORKERS)]
            return jnp.stack(outs)
        world = CommWorld(num_vcis=N_WORKERS + 1)
        if mode == "endpoints":
            rt = CommRuntime(world, progress="per_vci", token_impl="data")
            wins = [world.create(f"w{w}", kind="rma", vci=w + 1)
                    for w in range(N_WORKERS)]
        elif mode == "par_win+vcis":
            rt = CommRuntime(world, progress="hybrid",
                             join_every=2 * N_WORKERS, token_impl="data")
            wins = [world.create(f"w{w}", kind="rma")
                    for w in range(N_WORKERS)]
        elif mode == "par_win+global_flush":
            # the correctness fallback: every flush does a global round
            rt = CommRuntime(world, progress="hybrid", join_every=1,
                             token_impl="data")
            wins = [world.create(f"w{w}", kind="rma")
                    for w in range(N_WORKERS)]
        else:
            raise ValueError(mode)
        fetched = [rt.get(bands[w], wins[w], axis="data", perm=perm)
                   for w in range(N_WORKERS)]
        flushed = [rt.flush(f_, wins[w]) for w, f_ in enumerate(fetched)]
        return rt.barrier(jnp.stack(flushed))

    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(None, None),
                              out_specs=P(None, None), check_vma=False))
    x = jnp.ones((N_WORKERS, band_elems), jnp.float32)
    return f, x


MODES = ["everywhere", "par_win+vcis", "par_win+global_flush", "endpoints"]


def build_busy_target(mode: str, burn_iters: int, mesh, band_elems=16384):
    """Figs. 15/16: the target is busy computing before its band is ready.

    The fetch's SOURCE value depends on a target-side compute chain of
    ``burn_iters`` matmuls — on OPA (software RMA) a busy target stalls
    completions; TPU ICI progresses RMA in hardware, so all modes degrade
    only by the unavoidable data dependency (the paper's UCX/IB curve).
    """
    n = mesh.size
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(bands, w):
        # target-side computation producing the band
        def burn(b):
            v = b[: 256].reshape(16, 16)
            for _ in range(burn_iters):
                v = jnp.tanh(v @ w)
            return b + jnp.sum(v) * 1e-9
        busy = [burn(bands[k]) for k in range(N_WORKERS)]
        if mode == "everywhere":
            fetched = [jax.lax.ppermute(b, "data", perm) for b in busy]
            return jnp.stack(fetched)
        world = CommWorld(num_vcis=N_WORKERS + 1)
        rt = CommRuntime(world, progress="hybrid", join_every=2 * N_WORKERS,
                         token_impl="data")
        wins = [world.create(f"w{k}", kind="rma") for k in range(N_WORKERS)]
        fetched = [rt.get(busy[k], wins[k], axis="data", perm=perm)
                   for k in range(N_WORKERS)]
        flushed = [rt.flush(f_, wins[k]) for k, f_ in enumerate(fetched)]
        return rt.barrier(jnp.stack(flushed))

    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(P(None, None), P()),
                              out_specs=P(None, None), check_vma=False))
    x = jnp.ones((N_WORKERS, band_elems), jnp.float32)
    w = jnp.eye(16, dtype=jnp.float32) * 0.5
    return f, x, w


def bench_busy_target(mesh):
    csv = CSV("ebms_busy_target")
    for burn in (0, 8, 64, 256):
        for mode in ("everywhere", "par_win+vcis"):
            f, x, w = build_busy_target(mode, burn, mesh)
            f(x, w)
            t = time_fn(lambda: block(f(x, w)))
            csv.add(mode=mode, burn_iters=burn,
                    us_per_fetch=t["median_s"] * 1e6 / N_WORKERS)
    csv.dump()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    mesh = mesh_1d(args.devices)
    csv = CSV("ebms_remote_fetch")
    for band in (1024, 65536, 1048576):  # 4KB .. 4MB bands
        for mode in MODES:
            f, x = build(mode, band, mesh)
            hlo = f.lower(x).compile().as_text()
            f(x)
            t = time_fn(lambda: block(f(x)))
            d = collective_critical_depth(hlo)
            csv.add(mode=mode, band_bytes=band * 4,
                    us_per_fetch=t["median_s"] * 1e6 / N_WORKERS,
                    critical_depth=d["critical_depth"],
                    parallelism=round(d["parallelism"], 3))
    csv.dump()
    bench_busy_target(mesh)


if __name__ == "__main__":
    main()
