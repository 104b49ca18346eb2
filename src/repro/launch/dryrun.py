import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 placeholder host devices.
Nothing here allocates device memory — inputs are ShapeDtypeStructs.

Per combination this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. jits train_step (train shape) or serve_step (+ prefill lowering for
     prefill shapes) with explicit in/out shardings,
  3. ``.lower().compile()`` — any sharding mismatch / unsupported collective
     fails loudly here,
  4. records memory_analysis(), cost_analysis() and the HLO collective
     schedule into a JSON report consumed by EXPERIMENTS.md §Dry-run and the
     roofline table (§Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod|--both]
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, config_for_shape
from repro.launch import inputs as I
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import build_roofline
from repro.train.trainer import make_train_step
from repro.serve.engine import make_serve_step


def _memory_dict(compiled) -> Optional[dict]:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        }
    except Exception:
        return None


def _cost_dict(compiled) -> Optional[dict]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception:
        return None


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool,
               keep_hlo: bool = False, opts: tuple = ()) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = config_for_shape(arch, shape_name)
    if opts:
        import dataclasses
        remats = [o for o in opts if o.startswith("remat:")]
        real = tuple(o for o in opts if not o.startswith("remat:"))
        if real:
            cfg = cfg.with_opts(*real)
        for r in remats:
            cfg = dataclasses.replace(cfg, remat=r.split(":", 1)[1])
        if "decode_cache" in cfg.opts:
            tp = 16  # model-axis size of both production meshes
            kv = cfg.num_kv_heads
            # only when the cache batch-shards over data (else the seq dim
            # stays sharded and expansion just doubles the gathered bytes —
            # measured regression on long_500k, EXPERIMENTS §Perf)
            batch_shards = shape.global_batch % tp == 0 \
                and shape.global_batch >= tp
            if (batch_shards and cfg.num_heads and kv and kv < tp
                    and tp % kv == 0 and cfg.num_heads % tp == 0):
                cfg = dataclasses.replace(cfg, decode_kv_expand=tp // kv)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.size
    t0 = time.time()

    if shape.kind == "train":
        state_struct = I.train_state_struct(cfg)
        state_sh = I.train_state_shardings(cfg, mesh)
        batch_struct, batch_sh = I.batch_struct_and_shardings(cfg, shape, mesh)
        step = make_train_step(cfg, mesh=mesh, comm="gspmd")
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(state_struct, batch_struct)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        # prefill lowers the full forward producing the cache
        from repro.serve.engine import make_prefill
        params_struct = I.params_struct(cfg)
        params_sh = I.params_shardings(cfg, mesh)
        batch_struct, batch_sh = I.batch_struct_and_shardings(cfg, shape, mesh)
        cache_struct = I.cache_struct(cfg, shape)
        cache_sh = I.cache_shardings(cfg, shape, mesh)
        fn = make_prefill(cfg, mesh)
        jitted = jax.jit(
            fn,
            in_shardings=(params_sh, batch_sh, cache_sh),
            out_shardings=(I.decode_token_sharding(cfg, shape, mesh), cache_sh),
            donate_argnums=(2,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_struct, batch_struct, cache_struct)
            compiled = lowered.compile()
    else:  # decode
        params_struct = I.params_struct(cfg)
        params_sh = I.params_shardings(cfg, mesh)
        tok_struct = I.decode_token_struct(cfg, shape)
        tok_sh = I.decode_token_sharding(cfg, shape, mesh)
        cache_struct = I.cache_struct(cfg, shape)
        cache_sh = I.cache_shardings(cfg, shape, mesh)
        fn = make_serve_step(cfg, mesh)
        jitted = jax.jit(
            fn,
            in_shardings=(params_sh, tok_sh, cache_sh),
            out_shardings=(tok_sh, cache_sh),
            donate_argnums=(2,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_struct, tok_struct, cache_struct)
            compiled = lowered.compile()

    hlo = compiled.as_text()
    mem = _memory_dict(compiled)
    cost = _cost_dict(compiled)
    rl = build_roofline(cfg, shape, mesh_name, chips, hlo, cost, mem)
    out = rl.row()
    out["requested_arch"] = arch
    out["compile_s"] = time.time() - t0
    out["status"] = "ok"
    if keep_hlo:
        out["hlo"] = hlo
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod AND multi-pod meshes")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--opt", default="",
                    help="comma-separated optimization toggles "
                         "(moe_dispatch,decode_cache,fsdp) — §Perf variants")
    ap.add_argument("--stable", action="store_true",
                    help="deterministic reports: drop wall-clock fields "
                         "(compile_s) so a re-run diffs clean against the "
                         "committed reports/dryrun_baseline — the CI "
                         "dryrun-drift job runs with this flag")
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                if opts:
                    tag += "__opt_" + "_".join(opts)
                try:
                    row = lower_pair(arch, shape, multi_pod=mp, opts=opts)
                    dom = row["dominant"]
                    print(f"[ok] {tag:55s} compile={row['compile_s']:.1f}s "
                          f"dom={dom} "
                          f"C/M/K={row['t_compute_s']:.3g}/"
                          f"{row['t_memory_s']:.3g}/"
                          f"{row['t_collective_s']:.3g}s", flush=True)
                except Exception as e:
                    failures += 1
                    row = {"requested_arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "fail", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
                if args.stable:
                    row.pop("compile_s", None)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(row, f, indent=1, default=str)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
