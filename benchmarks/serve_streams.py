"""Serve-path VCI streams — decode throughput vs. pool size.

G concurrently-decoding batches ("lanes") are traced into ONE program; each
lane's TP all-reduces, MoE combines and sampling gathers ride its own
per-purpose CommContexts, all drawn from one ``ServeCommPlan`` sharing one
``CommRuntime`` (so contexts that collide in the VCI pool chain on the same
ordering token and serialize — the serve-side Fig. 17). Sweeping
``num_vcis`` from 1 (everything on the fallback stream: the paper's "one
global stream" anti-pattern, Fig. 4) up past the live context count shows
where the decode-throughput headroom lives.

Reported per cell: decode tok/s, ms/step, HLO collective count + critical
depth (the structural metric that transfers to the TPU target), and the
realized pool statistics.

The ENGINE cells (``engine_rows``) run the full continuous-batching
``ServeEngine`` under mixed-length traffic — a page pool sized to the
live-token budget vs the default full pool, at VCI pool sizes 1/4/8 — and
report end-to-end tok/s plus ``cache_bytes_resident``: finished slots'
pages reclaim immediately and admission allocates on entry, so the sized
pool holds the SAME tokens in fewer resident bytes than the full one
(``1 + batch x max_len / page`` pages).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks.common import CSV, SMOKE, block, emit_json, time_fn
from repro.configs import get_config
from repro.launch.roofline import collective_critical_depth
from repro.models.transformer import Model, init_cache, init_params
from repro.serve.comm import ServeCommPlan, serve_cache_specs, \
    serve_param_specs, serve_tp_validate
from repro.serve.engine import Request, ServeEngine, greedy_sample, \
    make_prefill

MAX_LEN = 64
PROMPT = 16

# engine (continuous-batching) cells: mixed-length traffic. max_len stays
# at/below mixtral's sliding window so the MoE arch keeps a non-ring cache
# (ring caches have no paged layout and take the grouped fallback).
ENGINE_MAX_LEN = 64
ENGINE_BATCH = 4
ENGINE_PAGE = 8
ENGINE_PAGES = 17           # 16 allocatable pages = 128 live-token slots


def serve_mesh(devices: int, tp: int = 2) -> Mesh:
    devs = jax.devices()
    if len(devs) < devices:
        raise RuntimeError(f"need {devices} devices, have {len(devs)} — run "
                           f"via benchmarks.run or set XLA_FLAGS")
    return Mesh(np.array(devs[:devices]).reshape(devices // tp, tp),
                ("data", "model"))


def make_multilane_step(cfg, mesh, plan: ServeCommPlan, lanes: int):
    """One traced decode step advancing ``lanes`` independent batches; lane
    g's collectives are issued on lane g's contexts, one shared runtime."""
    tp = dict(mesh.shape)["model"]
    serve_tp_validate(cfg, tp)
    nb = dict(mesh.shape)["data"]

    def step(params, toks, caches):
        bd = "data" if toks[0].shape[0] % nb == 0 else None
        nshard = nb if bd is not None else 1

        def inner(params, toks, caches):
            rt = plan.runtime()
            out_t, out_c = [], []
            for g in range(lanes):
                comm = plan.comm(g, rt=rt)
                model = Model(cfg, None, comm=comm)
                logits, nc = model.decode_step(params, toks[g], caches[g])
                out_t.append(greedy_sample(logits))
                out_c.append(nc)
            out_t[0] = rt.barrier(out_t[0])  # drain every stream
            return tuple(out_t), tuple(out_c)

        cspecs = tuple(serve_cache_specs(c, tp, nshard) for c in caches)
        f = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(serve_param_specs(cfg, params, tp),
                      tuple(P(bd, None) for _ in toks), cspecs),
            out_specs=(tuple(P(bd, None) for _ in toks), cspecs),
            check_vma=False, axis_names=set(mesh.axis_names))
        return f(params, toks, caches)

    return step


def run_cell(cfg, params, mesh, *, batch: int, lanes: int, num_vcis: int,
             policy: str, steps: int):
    plan = ServeCommPlan(num_vcis=num_vcis, vci_policy=policy, lanes=lanes,
                         token_impl="data")
    rng = np.random.default_rng(0)
    prefill = jax.jit(make_prefill(cfg, mesh, plan))
    toks, caches = [], []
    with jax.set_mesh(mesh):
        for g in range(lanes):
            prompts = rng.integers(0, cfg.vocab_size, (batch, PROMPT),
                                   dtype=np.int32)
            cache = init_cache(cfg, batch, MAX_LEN, dtype=jnp.float32)
            nxt, cache = prefill(params, {"tokens": jnp.asarray(prompts)},
                                 cache, jnp.zeros((batch,), jnp.int32),
                                 jnp.zeros((batch,), jnp.float32),
                                 jax.random.PRNGKey(g))
            toks.append(nxt)
            caches.append(cache)
        toks, caches = tuple(toks), tuple(caches)
        jitted = jax.jit(make_multilane_step(cfg, mesh, plan, lanes))
        hlo = jitted.lower(params, toks, caches).compile().as_text()

        def run():
            t, c = toks, caches
            for _ in range(steps):
                t, c = jitted(params, t, c)
            block((t, c))

        t = time_fn(run, reps=3 if SMOKE else 7)
    d = collective_critical_depth(hlo)
    ms_per_step = t["median_s"] * 1e3 / steps
    return {
        "ms_per_step": ms_per_step,
        "tok_s": lanes * batch / (ms_per_step / 1e3),
        "collectives": d["collective_count"],
        "critical_depth": d["critical_depth"],
        "parallelism": round(d["parallelism"], 3),
        "fallback_hits": plan.stats.fallback_hits,
        "max_ctx_per_vci": plan.stats.max_contexts_per_vci,
    }


def engine_requests(cfg, n: int, max_new: int):
    """Mixed-length traffic: prompt lengths in [8, 16] — the --vary-prompts
    shape the left-padded continuous path exists for."""
    rng = np.random.default_rng(1)
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(8, 17)),),
                                        dtype=np.int32),
                    max_new_tokens=max_new) for _ in range(n)]


def run_engine_cell(cfg, params, mesh, *, sized: bool, num_vcis: int,
                    requests: int, max_new: int):
    """End-to-end continuous batching: #requests > batch_size so slots
    recycle mid-stream (admission runs under the mesh)."""
    plan = ServeCommPlan(num_vcis=num_vcis, token_impl="data")
    eng = ServeEngine(cfg, params, batch_size=ENGINE_BATCH,
                      max_len=ENGINE_MAX_LEN, mesh=mesh, comm_plan=plan,
                      page_size=ENGINE_PAGE,
                      num_pages=ENGINE_PAGES if sized else None)
    assert eng._padded_ok, "the engine fell back to grouped batches"
    eng.generate(engine_requests(cfg, requests, max_new))  # compile warmup
    t = time_fn(lambda: eng.generate(engine_requests(cfg, requests, max_new)),
                warmup=0, reps=2 if SMOKE else 3, min_time_s=0.0)
    n_tok = requests * max_new
    return {
        "pool": "sized" if sized else "full",
        "tok_s": n_tok / t["median_s"],
        "cache_bytes_resident": eng.cache_bytes_resident,
        "admit_under_mesh": bool(eng._admit_fns),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--policy", default="fcfs")
    ap.add_argument("--steps", type=int, default=None,
                    help="decode steps per timed call")
    args = ap.parse_args()
    mesh = serve_mesh(args.devices, args.tp)
    steps = args.steps or (2 if SMOKE else 8)

    archs = ("olmo-1b-smoke", "mixtral-8x22b-smoke")
    batches = (4,) if SMOKE else (4, 8)
    vcis = (1, 8) if SMOKE else (1, 2, 4, 8)

    csv = CSV("serve_streams")
    rows = []
    for arch in archs:
        cfg = get_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        for batch in batches:
            for nv in vcis:
                r = run_cell(cfg, params, mesh, batch=batch,
                             lanes=args.lanes, num_vcis=nv,
                             policy=args.policy, steps=steps)
                row = dict(arch=arch, batch=batch, lanes=args.lanes,
                           num_vcis=nv, policy=args.policy, **r)
                rows.append(row)
                csv.add(**row)
    csv.dump()

    def cell(arch, batch, nv):
        return next(r for r in rows if r["arch"] == arch
                    and r["batch"] == batch and r["num_vcis"] == nv)

    # engine-level sized-vs-full pool cells under mixed-length traffic
    eng_vcis = (1, 8) if SMOKE else (1, 4, 8)
    requests = 6 if SMOKE else 8
    max_new = 4 if SMOKE else 8
    eng_csv = CSV("serve_engine_paged")
    engine_rows = []
    for arch in archs:
        cfg = get_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        for sized in (False, True):
            for nv in eng_vcis:
                r = run_engine_cell(cfg, params, mesh, sized=sized,
                                    num_vcis=nv, requests=requests,
                                    max_new=max_new)
                row = dict(arch=arch, num_vcis=nv,
                           batch=ENGINE_BATCH, max_len=ENGINE_MAX_LEN,
                           requests=requests, max_new=max_new, **r)
                engine_rows.append(row)
                eng_csv.add(**row)
    eng_csv.dump()

    def eng_cell(arch, pool, nv):
        return next(r for r in engine_rows if r["arch"] == arch
                    and r["pool"] == pool and r["num_vcis"] == nv)

    # CPU-host wall clock is a PROXY (see benchmarks.common): tok/s cells
    # are reported per pool size, but the metric that transfers to the TPU
    # target is the collective critical depth — dedicated streams must
    # shorten it vs the single fallback stream.
    summary = {}
    for arch in archs:
        for batch in batches:
            lo = cell(arch, batch, vcis[0])
            hi = cell(arch, batch, max(vcis))
            summary[f"{arch}/b{batch}"] = {
                "tok_s_1vci": lo["tok_s"],
                "tok_s_maxvci": hi["tok_s"],
                "speedup": hi["tok_s"] / lo["tok_s"],
                "depth_1vci": lo["critical_depth"],
                "depth_maxvci": hi["critical_depth"],
            }
    # the sized-pool acceptance cell: same tokens, fewer resident bytes
    engine_summary = {}
    for arch in archs:
        for nv in eng_vcis:
            f = eng_cell(arch, "full", nv)
            p = eng_cell(arch, "sized", nv)
            engine_summary[f"{arch}/vcis{nv}"] = {
                "tok_s_full": f["tok_s"],
                "tok_s_sized": p["tok_s"],
                "cache_bytes_full": f["cache_bytes_resident"],
                "cache_bytes_sized": p["cache_bytes_resident"],
                "cache_bytes_ratio": (p["cache_bytes_resident"]
                                      / f["cache_bytes_resident"]),
            }
    emit_json("serve_streams", {"rows": rows, "engine_rows": engine_rows,
                                "summary": summary,
                                "engine_summary": engine_summary,
                                "mesh": {"devices": args.devices,
                                         "tp": args.tp,
                                         "lanes": args.lanes}})


if __name__ == "__main__":
    main()
