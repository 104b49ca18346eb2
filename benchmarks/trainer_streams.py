"""Trainer-level VCI stream scaling — the paper's message-rate claim
exercised through the REAL training API (not a microbenchmark).

``make_train_step(comm="vci", num_streams=K, progress=...)`` buckets the
gradient pytree onto K CommContexts; this sweeps K and the progress model
and reports the compiled step's collective structure + wall clock. The
paper's story at this level: serialized streams (global progress) keep
K chained reductions; independent streams let XLA combine/overlap them.

The fast-path knobs ride along: ``--pack``/``--reduction``/``--per-step-plan``
select the bucketed-reduction implementation (see ``benchmarks.bucket_path``
for the dedicated 3-knob ablation of that hot path), and ``--optimizer
zero1`` swaps in the ZeRO-1 sharded AdamW (reduce_scatter shards consumed
directly, updated params all-gathered — half the gradient wire bytes).
"""

from __future__ import annotations

import argparse

import jax

from benchmarks.common import CSV, SMOKE, block, mesh_1d, time_fn
from repro.configs import get_config
from repro.data.pipeline import synthetic_batch
from repro.launch.roofline import collective_critical_depth
from repro.train.trainer import make_train_step, train_state_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--pack", default="xla", choices=("xla", "pallas"))
    ap.add_argument("--reduction", default="all_reduce",
                    choices=("all_reduce", "reduce_scatter"))
    ap.add_argument("--per-step-plan", action="store_true",
                    help="seed behaviour: rebuild the comm plan every trace")
    ap.add_argument("--optimizer", default="replicated",
                    choices=("replicated", "zero1"),
                    help="zero1 = ZeRO-1 sharded AdamW (reduce_scatter "
                         "shards in, updated-param all_gather out)")
    ap.add_argument("--zero1-wire", default=None,
                    help="zero1 wire dtype (e.g. bfloat16); default f32")
    args = ap.parse_args()
    mesh = mesh_1d(args.devices)
    cfg = get_config("olmo-1b-smoke")
    batch = synthetic_batch(cfg, 2 * mesh.size, 32, seed=0)

    progresses = ("hybrid",) if SMOKE else ("global", "hybrid", "per_vci")
    stream_counts = (1, 4) if SMOKE else (1, 2, 4, 8)

    csv = CSV("trainer_vci_streams")
    for progress in progresses:
        for streams in stream_counts:
            state = train_state_init(cfg, jax.random.PRNGKey(0),
                                     optimizer=args.optimizer, mesh=mesh,
                                     num_streams=streams, pack=args.pack)
            step = make_train_step(cfg, mesh=mesh, comm="vci",
                                   num_streams=streams,
                                   num_vcis=streams + 1,
                                   progress=progress, token_impl="data",
                                   pack=args.pack, reduction=args.reduction,
                                   persistent_plan=not args.per_step_plan,
                                   optimizer=args.optimizer,
                                   zero1_wire_dtype=args.zero1_wire)
            with jax.set_mesh(mesh):
                jitted = jax.jit(step)
                compiled = jitted.lower(state, batch).compile()
                hlo = compiled.as_text()
                jitted(state, batch)
                t = time_fn(lambda: block(jitted(state, batch)), reps=5)
            d = collective_critical_depth(hlo)
            csv.add(progress=progress, streams=streams, pack=args.pack,
                    reduction=args.reduction, optimizer=args.optimizer,
                    ms_per_step=t["median_s"] * 1e3,
                    collectives=d["collective_count"],
                    critical_depth=d["critical_depth"])
    csv.dump()


if __name__ == "__main__":
    main()
