"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b-smoke \
        --steps 50 --batch 8 --seq 128 --comm vci --progress hybrid

Runs on whatever devices are visible (1 CPU here; a real TPU slice in
production — the same code path, with ``--mesh`` picking the production
topology). ``--comm vci`` engages the paper's bucketed VCI gradient
reduction; ``--comm gspmd`` is the XLA-native baseline.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint.io import latest_step, load_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import synthetic_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.schedule import cosine_schedule
from repro.train.trainer import make_train_step, train_state_init


def build_mesh(spec: str):
    if spec == "none" or not spec:
        return None
    from jax.sharding import Mesh
    dims = [int(d) for d in spec.split("x")]
    names = {1: ("data",), 2: ("data", "model"),
             3: ("pod", "data", "model")}[len(dims)]
    devs = np.array(jax.devices()[: int(np.prod(dims))]).reshape(dims)
    return Mesh(devs, names)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b-smoke",
                    help=f"one of {ARCH_IDS} (+ -smoke / -swa<W> suffixes)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="none", help='e.g. "8" or "4x2"')
    ap.add_argument("--comm", choices=("gspmd", "vci"), default="gspmd")
    ap.add_argument("--progress", choices=("global", "per_vci", "hybrid"),
                    default="hybrid")
    ap.add_argument("--vci-policy", default="fcfs")
    ap.add_argument("--num-streams", type=int, default=8)
    ap.add_argument("--pack", choices=("xla", "pallas"), default="xla",
                    help="bucket pack impl: concat chain vs tile-DMA layout")
    ap.add_argument("--reduction", choices=("all_reduce", "reduce_scatter"),
                    default="all_reduce")
    ap.add_argument("--optimizer", choices=("replicated", "zero1"),
                    default="replicated",
                    help="zero1 = ZeRO-1 sharded AdamW consuming the "
                         "reduce_scatter shards directly (vci mode only)")
    ap.add_argument("--zero1-wire", default=None,
                    help="wire dtype for zero1 grad-scatter/param-gather "
                         "(e.g. bfloat16); default f32")
    ap.add_argument("--overlap", action="store_true",
                    help="bucket-ready overlap scheduling (vci mode only): "
                         "issue each bucket's reduce inside the backward on "
                         "its VCI stream as soon as its grads exist, instead "
                         "of one post-backward reduction pass")
    ap.add_argument("--per-step-plan", action="store_true",
                    help="rebuild the comm plan every trace (seed behaviour; "
                         "default uses the persistent CommPlan cache)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    mesh = build_mesh(args.mesh)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={len(jax.devices())} mesh={args.mesh} comm={args.comm}")

    lr_fn = lambda s: cosine_schedule(s, peak=args.lr,
                                      warmup_steps=args.warmup,
                                      total_steps=args.steps)
    schedule = "overlap" if args.overlap else "post"
    step_fn = make_train_step(
        cfg, mesh=mesh, lr_fn=lr_fn, comm=args.comm, accum_steps=args.accum,
        num_streams=args.num_streams, progress=args.progress,
        vci_policy=args.vci_policy,
        pack=args.pack, reduction=args.reduction,
        persistent_plan=not args.per_step_plan,
        optimizer=args.optimizer, zero1_wire_dtype=args.zero1_wire,
        schedule=schedule,
        token_impl="data" if jax.default_backend() == "cpu" else "barrier")
    step = jax.jit(step_fn)

    state = train_state_init(
        cfg, jax.random.PRNGKey(args.seed), optimizer=args.optimizer,
        mesh=mesh, num_streams=args.num_streams, pack=args.pack,
        schedule=schedule)
    start = 0
    if args.ckpt_dir and (ls := latest_step(args.ckpt_dir)) is not None:
        state = load_checkpoint(args.ckpt_dir, ls, state)
        start = ls
        print(f"resumed from step {ls}")

    t0 = time.time()
    tokens_done = 0
    for i in range(start, args.steps):
        batch = synthetic_batch(cfg, args.batch, args.seq, seed=args.seed,
                                step=i)
        state, metrics = step(state, batch)
        tokens_done += args.batch * args.seq
        if (i + 1) % args.log_every == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            dt = time.time() - t0
            print(f"step {i+1:5d}  loss {loss:7.4f}  ce {float(metrics['ce']):7.4f}  "
                  f"gnorm {float(metrics['grad_norm']):6.3f}  "
                  f"tok/s {tokens_done/dt:9.0f}", flush=True)
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, state,
                            metadata={"arch": cfg.name})
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state,
                        metadata={"arch": cfg.name})
        print(f"checkpoint -> {args.ckpt_dir}")


if __name__ == "__main__":
    main()
