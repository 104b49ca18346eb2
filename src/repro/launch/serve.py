"""Serving driver: continuous-batching prefill + decode on any architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b-smoke \
        --requests 8 --prompt-len 32 --max-new 32

Serve-path VCI streams (manual TP, collectives on per-purpose CommContexts):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b-smoke \
        --tp 2 --num-vcis 8 --policy fcfs --temperature 0.8 --stop 17

Mixed prompt lengths on a right-sized page pool (pool of fixed-size pages +
per-slot page table; slots recycle mid-stream, under the mesh too):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b-smoke \
        --tp 2 --vary-prompts --page-size 16 --pages 40
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.serve.comm import ServeCommPlan
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--vary-prompts", action="store_true",
                    help="draw prompt lengths in [prompt-len/2, prompt-len] "
                         "to exercise the left-padded mixed-length path")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="engine-default sampling temperature (0 = greedy)")
    ap.add_argument("--stop", type=int, default=None,
                    help="stop token id applied to every request")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree; >1 builds a (data, model) "
                         "mesh and runs decode on VCI streams")
    ap.add_argument("--num-vcis", type=int, default=8,
                    help="VCI pool size for the serve comm plan (tp>1)")
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "round_robin", "hash", "hinted"),
                    help="VCI pool assignment policy (tp>1)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page of the paged cache")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size incl. the trash page (default: "
                         "full provision batch*ceil(max_len/page_size)+1)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    mesh = comm_plan = None
    if args.tp > 1:
        from jax.sharding import Mesh
        devs = jax.devices()
        if len(devs) % args.tp:
            raise SystemExit(f"{len(devs)} devices do not split into tp="
                             f"{args.tp} (set XLA_FLAGS host device count)")
        mesh = Mesh(np.array(devs).reshape(len(devs) // args.tp, args.tp),
                    ("data", "model"))
        comm_plan = ServeCommPlan(num_vcis=args.num_vcis,
                                  vci_policy=args.policy)
        print(f"mesh=data{mesh.shape['data']}xmodel{args.tp} "
              f"num_vcis={args.num_vcis} policy={args.policy}")

    engine = ServeEngine(cfg, params, batch_size=args.batch,
                         max_len=args.max_len, mesh=mesh,
                         comm_plan=comm_plan, temperature=args.temperature,
                         seed=args.seed, page_size=args.page_size,
                         num_pages=args.pages)
    if engine._padded_ok:
        print(f"paged cache: page_size={args.page_size} "
              f"num_pages={engine._num_pages}")

    rng = np.random.default_rng(args.seed)
    reqs = []
    for _ in range(args.requests):
        plen = (int(rng.integers(max(1, args.prompt_len // 2),
                                 args.prompt_len + 1))
                if args.vary_prompts else args.prompt_len)
        shape = ((cfg.num_codebooks, plen)
                 if cfg.modality == "audio" else (plen,))
        reqs.append(Request(
            prompt=rng.integers(0, cfg.vocab_size, shape, dtype=np.int32),
            max_new_tokens=args.max_new, stop_token=args.stop))

    t0 = time.time()
    done = engine.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(r.generated.shape[-1] for r in done)
    print(f"{len(done)} requests, {n_tok} new tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s) "
          f"cache_bytes_resident={engine.cache_bytes_resident}")
    if comm_plan is not None:
        s = comm_plan.stats
        print(f"vci stats: acquires={s.acquires} fallback_hits="
              f"{s.fallback_hits} max_contexts_per_vci="
              f"{s.max_contexts_per_vci} map={comm_plan.vci_map()}")
    for i, r in enumerate(done[:4]):
        tail = r.generated[..., :8]
        print(f"  req{i}: first tokens {tail.tolist()}")


if __name__ == "__main__":
    main()
