"""Multi-device correctness checks, run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set by the caller —
tests/conftest.py — BEFORE python starts, so the main pytest process keeps
its single real device).

Each ``check_*`` function is independent; ``main`` runs those named on the
command line (or all) and prints ``PASS <name>`` / ``FAIL <name>: err``.
"""

import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bucketing import plan_buckets, reduce_gradients
from repro.core.collectives import CommRuntime
from repro.core.comm import CommWorld


def _mesh1d(n=None):
    devs = jax.devices()
    n = n or len(devs)
    return Mesh(np.array(devs[:n]), ("data",))


# ---------------------------------------------------------------------------
def check_collectives_numerics():
    """CommRuntime collectives == plain lax collectives, all progress modes."""
    mesh = _mesh1d()
    n = mesh.size
    x = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)

    for progress in ("global", "per_vci", "hybrid"):
        def run(x):
            world = CommWorld(num_vcis=4)
            rt = CommRuntime(world, progress=progress, join_every=2)
            c1 = world.create("c1")
            c2 = world.create("c2")
            w = world.create("w", kind="rma")
            ar = rt.all_reduce(x, c1, axis="data")
            ag = rt.all_gather(x, c2, axis="data")
            rs = rt.reduce_scatter(ag, c1, axis="data")
            a2a = rt.all_to_all(
                jnp.broadcast_to(x, (n,) + x.shape), c2, axis="data",
                split_axis=0, concat_axis=1)
            perm = [(i, (i + 1) % n) for i in range(n)]
            sr = rt.sendrecv(x, c1, axis="data", perm=perm)
            acc = rt.accumulate(x, w, axis="data")
            return rt.barrier((ar, ag, rs, a2a, sr, acc))

        f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False))
        ar, ag, rs, a2a, sr, acc = f(x)
        np.testing.assert_allclose(ar, jnp.broadcast_to(x.sum(0), (n, 4)))
        np.testing.assert_allclose(ag.reshape(n, n, 4)[0], x)
        np.testing.assert_allclose(rs, x * n)
        np.testing.assert_allclose(sr, jnp.roll(x, 1, axis=0))
        np.testing.assert_allclose(acc, jnp.broadcast_to(x.sum(0), (n, 4)))
        assert a2a.shape == (n, n, 4)


def check_accumulate_relaxed_matches_ordered():
    """accumulate_ordering=none (§6.3 hint) changes scheduling, not values."""
    mesh = _mesh1d()
    n = mesh.size
    x = jnp.arange(n * 8, dtype=jnp.float32).reshape(n, 8)

    outs = {}
    for ordering in ("rar", "none"):
        def run(x):
            world = CommWorld(num_vcis=4)
            rt = CommRuntime(world, progress="hybrid")
            w = world.create("w", kind="rma", accumulate_ordering=ordering)
            a = rt.accumulate(x, w, axis="data")
            b = rt.accumulate(x * 2, w, axis="data")
            return rt.barrier(a + b)
        f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False))
        outs[ordering] = np.asarray(f(x))
    np.testing.assert_allclose(outs["rar"], outs["none"])


def check_reduce_gradients_matches_pmean():
    """Bucketed VCI reduction == tree-wise pmean, both staging modes."""
    mesh = _mesh1d()
    n = mesh.size
    rng = np.random.default_rng(0)
    tree = {
        "a": jnp.asarray(rng.normal(size=(n, 16, 8)), jnp.float32),
        "b": {"w": jnp.asarray(rng.normal(size=(n, 130)), jnp.float32),
              "s": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)},
    }
    # per-shard leaves keep their leading (1, ...) dim; the mean over 'data'
    # replicates, so the global result is mean-with-keepdims.
    expect = jax.tree_util.tree_map(lambda t: t.mean(0, keepdims=True), tree)

    for staging in ("per_vci", "shared"):
        for progress in ("global", "per_vci", "hybrid"):
            def run(tr):
                world = CommWorld(num_vcis=4)
                rt = CommRuntime(world, progress=progress, join_every=3)
                plan = plan_buckets(tr, 3, align=8)
                red = reduce_gradients(rt, tr, plan, axis="data", mean=True,
                                       staging=staging)
                return rt.barrier(red)
            f = jax.jit(jax.shard_map(
                run, mesh=mesh,
                in_specs=(jax.tree_util.tree_map(lambda _: P("data"), tree),),
                out_specs=jax.tree_util.tree_map(lambda _: P(), tree),
                check_vma=False))
            got = f(tree)
            for g, e in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(expect)):
                np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-6)


def check_bucket_fastpath_matches_pmean():
    """Every fast-path cell (pack x reduction x plan persistence) must equal
    tree-wise pmean — the numerical acceptance gate for the bucketed fast
    path (persistent CommPlan, pallas/DMA pack, reduce_scatter+all_gather)."""
    from repro.core import get_comm_plan, plan_cache_clear, plan_cache_stats
    from repro.core.bucketing import reduce_gradients as reduce_g

    mesh = _mesh1d()
    n = mesh.size
    rng = np.random.default_rng(7)
    tree = {
        "a": jnp.asarray(rng.normal(size=(n, 16, 8)), jnp.float32),
        "b": {"w": jnp.asarray(rng.normal(size=(n, 130)), jnp.float32),
              "s": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)},
        "c": jnp.asarray(rng.normal(size=(n, 257)), jnp.bfloat16),
    }
    expect = jax.tree_util.tree_map(
        lambda t: jnp.asarray(t, jnp.float32).mean(0, keepdims=True)
        .astype(t.dtype), tree)

    plan_cache_clear()
    for pack in ("xla", "pallas"):
        for reduction in ("all_reduce", "reduce_scatter"):
            for persistent in (True, False):
                def run(tr):
                    cp = get_comm_plan(tr, num_streams=3, num_vcis=4,
                                       pack=pack, persistent=persistent)
                    rt = cp.runtime()
                    red = reduce_g(rt, tr, cp, axis="data", mean=True,
                                   pack=pack, reduction=reduction)
                    return rt.barrier(red)

                f = jax.jit(jax.shard_map(
                    run, mesh=mesh,
                    in_specs=(jax.tree_util.tree_map(lambda _: P("data"),
                                                     tree),),
                    out_specs=jax.tree_util.tree_map(lambda _: P(), tree),
                    check_vma=False))
                got = f(tree)
                for g, e in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(expect)):
                    np.testing.assert_allclose(
                        np.asarray(g, np.float32), np.asarray(e, np.float32),
                        rtol=1e-5, atol=1e-5)
    # the persistent cells must actually have reused cached plans
    assert plan_cache_stats()["hits"] >= 2, plan_cache_stats()


def check_zero1_matches_replicated():
    """ZeRO-1 conformance: 5 steps of ``make_train_step(optimizer="zero1")``
    (reduce_scatter shards -> sharded AdamW -> param all_gather) must match
    the replicated path to fp32 tolerance on the 8-device mesh, for a dense
    config (gemma) AND an MoE config (mixtral). Smoke configs carry f32
    params, so with the default f32 wire the two paths differ only in
    collective summation order."""
    from repro.configs import get_config
    from repro.data.pipeline import synthetic_batch
    from repro.train.trainer import make_train_step, train_state_init

    mesh = _mesh1d()
    n = mesh.size
    for arch in ("gemma-2b-smoke", "mixtral-8x22b-smoke"):
        cfg = get_config(arch)
        knobs = dict(mesh=mesh, comm="vci", num_streams=4, num_vcis=4,
                     token_impl="data")
        step_rep = make_train_step(cfg, **knobs)
        step_z1 = make_train_step(cfg, optimizer="zero1", **knobs)
        s_rep = train_state_init(cfg, jax.random.PRNGKey(0))
        s_z1 = train_state_init(cfg, jax.random.PRNGKey(0),
                                optimizer="zero1", mesh=mesh, num_streams=4)
        # zero1 optimizer state is genuinely 1/N per rank
        shard_elems = sum(m.size for m in s_z1.opt.m) // n
        full_elems = sum(l.size for l in jax.tree_util.tree_leaves(s_rep.opt.m))
        assert shard_elems < full_elems, (shard_elems, full_elems)

        with jax.set_mesh(mesh):
            jr, jz = jax.jit(step_rep), jax.jit(step_z1)
            for i in range(5):
                batch = synthetic_batch(cfg, 2 * n, 32, seed=i)
                s_rep, m_rep = jr(s_rep, batch)
                s_z1, m_z1 = jz(s_z1, batch)
                for k in ("loss", "grad_norm"):
                    np.testing.assert_allclose(
                        float(m_z1[k]), float(m_rep[k]), rtol=1e-5,
                        err_msg=f"{arch} step {i} metric {k}")
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_leaves_with_path(s_z1.params),
                jax.tree_util.tree_leaves_with_path(s_rep.params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-5, atol=1e-6,
                err_msg=f"{arch} param {jax.tree_util.keystr(pa)}")


def check_overlap_matches_post():
    """Bucket-ready overlap scheduling conformance: 5 train steps with
    ``schedule="overlap"`` (each bucket's reduce issued inside the backward
    via its custom_vjp boundary) must match ``schedule="post"`` (one
    post-backward reduction pass) to fp32 tolerance on the 8-device mesh,
    for a dense config (gemma) AND an MoE config (mixtral), for BOTH
    ``optimizer="replicated"`` and ``"zero1"``, including microbatch
    accumulation (the dense configs run accum_steps=2: only the last
    microbatch's backward carries the boundaries, earlier microbatches ride
    in as the carry)."""
    from repro.configs import get_config
    from repro.data.pipeline import synthetic_batch
    from repro.train.trainer import make_train_step, train_state_init

    mesh = _mesh1d()
    n = mesh.size
    lr = 3e-4
    # The schedules sum each bucket in a different order. Where a gradient
    # element is a cancellation residue near zero, that last-bit change
    # survives AdamW's normalisation m/sqrt(v): the element's update can then
    # differ by a sizeable fraction of one bias-corrected step, whose size is
    # lr. Parameters may differ by up to one such step; a schedule bug moves
    # many elements and shows first in the loss/grad_norm check at 1e-5.
    param_atol = lr
    for arch in ("gemma-2b-smoke", "mixtral-8x22b-smoke"):
        cfg = get_config(arch)
        accum = 2 if arch.startswith("gemma") else 1
        for optimizer in ("replicated", "zero1"):
            knobs = dict(mesh=mesh, comm="vci", num_streams=4, num_vcis=4,
                         token_impl="data", accum_steps=accum,
                         optimizer=optimizer, lr_fn=lambda step: lr)
            states, steps = {}, {}
            for sched in ("post", "overlap"):
                steps[sched] = make_train_step(cfg, schedule=sched, **knobs)
                states[sched] = train_state_init(
                    cfg, jax.random.PRNGKey(0), optimizer=optimizer,
                    mesh=mesh, num_streams=4, schedule=sched)
            with jax.set_mesh(mesh):
                jits = {s: jax.jit(f) for s, f in steps.items()}
                for i in range(5):
                    batch = synthetic_batch(cfg, 2 * n, 32, seed=i)
                    metrics = {}
                    for sched in ("post", "overlap"):
                        states[sched], metrics[sched] = jits[sched](
                            states[sched], batch)
                    for k in ("loss", "grad_norm"):
                        np.testing.assert_allclose(
                            float(metrics["overlap"][k]),
                            float(metrics["post"][k]), rtol=1e-5,
                            err_msg=f"{arch} {optimizer} step {i} "
                                    f"metric {k}")
            for (pa, a), (pb, b) in zip(
                    jax.tree_util.tree_leaves_with_path(
                        states["overlap"].params),
                    jax.tree_util.tree_leaves_with_path(
                        states["post"].params)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=2e-5, atol=param_atol,
                    err_msg=f"{arch} {optimizer} param "
                            f"{jax.tree_util.keystr(pa)}")


def check_vci_train_step_matches_gspmd():
    """comm='vci' (paper mode) and comm='gspmd' produce the same update."""
    from repro.configs import get_config
    from repro.data.pipeline import synthetic_batch
    from repro.train.trainer import make_train_step, train_state_init

    mesh = _mesh1d()
    n = mesh.size
    cfg = get_config("olmo-1b-smoke")
    batch = synthetic_batch(cfg, 2 * n, 32, seed=1)
    state = train_state_init(cfg, jax.random.PRNGKey(0))

    with jax.set_mesh(mesh):
        ref_step = jax.jit(make_train_step(cfg, mesh=None, comm="gspmd"))
        s_ref, m_ref = ref_step(state, batch)

    for progress in ("hybrid", "per_vci", "global"):
        step = make_train_step(cfg, mesh=mesh, comm="vci", num_streams=4,
                               num_vcis=4, progress=progress,
                               token_impl="data")
        with jax.set_mesh(mesh):
            s_vci, m_vci = jax.jit(step)(state, batch)
        np.testing.assert_allclose(
            float(m_vci["loss"]), float(m_ref["loss"]), rtol=1e-5)
        # bf16 params + different reduction order: one bf16 ULP is
        # 2^-8 ~= 3.9e-3, so rtol must sit above it (seed's 2e-3 flaked on
        # elements exactly one ULP apart); 5e-3 = 1.28 ULP headroom.
        for a, b in zip(jax.tree_util.tree_leaves(s_vci.params),
                        jax.tree_util.tree_leaves(s_ref.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-3, atol=5e-6)


def check_scan_vs_unroll_collective_parity():
    """Roofline HLO parser: scan-over-layers must count L x the collectives
    of one layer — parity with the unrolled version of the same model."""
    from repro.launch.roofline import parse_collectives

    mesh = _mesh1d()
    L, d = 4, 8

    def layer(x, w):
        y = x @ w
        return jax.lax.psum(y, "data")

    def scanned(x, ws):
        def body(c, w):
            return layer(c, w), None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    def unrolled(x, ws):
        for i in range(L):
            x = layer(x, ws[i])
        return x

    x = jnp.zeros((2, d))
    ws = jnp.zeros((L, d, d))
    spec_in = (P(), P())
    f_s = jax.jit(jax.shard_map(scanned, mesh=mesh, in_specs=spec_in,
                                out_specs=P(), check_vma=False))
    f_u = jax.jit(jax.shard_map(unrolled, mesh=mesh, in_specs=spec_in,
                                out_specs=P(), check_vma=False))
    n = mesh.size
    hlo_s = f_s.lower(x, ws).compile().as_text()
    hlo_u = f_u.lower(x, ws).compile().as_text()
    b_s = sum(op.link_bytes for op in parse_collectives(hlo_s, n))
    b_u = sum(op.link_bytes for op in parse_collectives(hlo_u, n))
    assert b_u > 0, "unrolled model lost its collectives"
    assert abs(b_s - b_u) / b_u < 0.01, (b_s, b_u)


def check_progress_mode_hlo_structure():
    """per_vci emits fewer cross-stream joins than hybrid; all modes keep
    every collective alive (drain prevents DCE)."""
    mesh = _mesh1d()

    def make(progress, join_every=1):
        def run(x):
            world = CommWorld(num_vcis=4)
            rt = CommRuntime(world, progress=progress, join_every=join_every)
            ctxs = [world.create(f"c{i}") for i in range(4)]
            outs = [rt.all_reduce(x + i, c, axis="data")
                    for i, c in enumerate(ctxs)]
            return rt.barrier(sum(outs))
        return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("data"),
                                     out_specs=P(), check_vma=False))

    x = jnp.ones((mesh.size, 4))
    for progress in ("global", "per_vci", "hybrid"):
        hlo = make(progress).lower(x).compile().as_text()
        assert hlo.count("all-reduce") >= 4 or "all-reduce" in hlo, progress
    # values identical across modes
    ref = None
    for progress in ("global", "per_vci", "hybrid"):
        val = np.asarray(make(progress)(x))
        if ref is None:
            ref = val
        np.testing.assert_allclose(val, ref)


def check_moe_expert_parallel_all_to_all():
    """The MoE dispatch under an expert-parallel mesh lowers all-to-all or
    equivalent resharding collectives, and numerics match the meshless run."""
    from repro.configs import get_config
    from repro.models.moe import moe_ffn
    from repro.models.transformer import init_params
    from repro.dist.sharding import Sharder

    cfg = get_config("mixtral-8x22b-smoke")  # 4 experts
    mesh = _mesh1d(4)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)

    y_ref, aux_ref = moe_ffn(cfg, x, lp, None, inference=True)

    shard = Sharder(mesh, cfg)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda x, p: moe_ffn(cfg, x, p, shard, inference=True)[0],
                    in_shardings=(NamedSharding(mesh, P("data")), None))
        y_sh = f(x, lp)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)





def check_serve_streams_match_single_stream():
    """Serve-path VCI streams (manual-TP decode on a data x model mesh,
    collectives on per-purpose CommContexts) must produce IDENTICAL tokens
    to the single-device engine, for a dense tied-embedding arch and an
    expert-parallel MoE arch, at num_vcis=1 (everything collides on the
    fallback stream) and num_vcis=8 (dedicated streams). Mixed-length
    batches ride along so left-padded prefill is exercised under TP too.

    The mesh engine has batch_size=2 < #requests, so mid-stream admission
    (page alloc + the shard-aware admission prefill + splice) runs UNDER
    the mesh, and a right-sized pool must hold fewer resident bytes than
    the single-device engine's full pool despite the extra page table."""
    from repro.configs import get_config
    from repro.models.transformer import init_params
    from repro.serve.comm import PURPOSES, ServeCommPlan
    from repro.serve.engine import Request, ServeEngine

    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("data", "model"))

    for arch in ("olmo-1b-smoke", "mixtral-8x22b-smoke"):
        cfg = get_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))

        def make_requests():
            rng = np.random.default_rng(7)
            return [Request(prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                                dtype=np.int32),
                            max_new_tokens=5)
                    for plen in (5, 9, 3, 7)]

        ref = make_requests()
        solo = ServeEngine(cfg, params, batch_size=4, max_len=48)
        solo.generate(ref)

        for num_vcis in (1, 8):
            plan = ServeCommPlan(num_vcis=num_vcis, token_impl="data")
            eng = ServeEngine(cfg, params, batch_size=2, max_len=48,
                              mesh=mesh, comm_plan=plan, page_size=8,
                              num_pages=11)
            got = make_requests()
            eng.generate(got)
            assert eng._admit_fns, "no request was admitted under the mesh"
            for i, (a, b) in enumerate(zip(got, ref)):
                np.testing.assert_array_equal(
                    a.generated, b.generated,
                    err_msg=f"{arch} num_vcis={num_vcis} request {i}")
            # the plan realized the expected mapping: exhausted pool -> all
            # contexts share the fallback; ample pool -> distinct streams
            indices = set(plan.vci_map().values())
            if num_vcis == 1:
                assert indices == {0}, plan.vci_map()
                assert plan.stats.fallback_hits == len(PURPOSES)
            else:
                assert len(indices) == len(PURPOSES), plan.vci_map()
                assert plan.stats.fallback_hits == 0
            owner = np.asarray(eng._owner)
            assert (owner[1:] == -1).all(), f"pages leaked: {owner}"
            assert eng.cache_bytes_resident < solo.cache_bytes_resident, (
                eng.cache_bytes_resident, solo.cache_bytes_resident)


def check_vci_trainer_lowers_production_mesh():
    """The paper-mode (shard_map + VCI buckets) trainer must lower/compile
    on the full production mesh (run with 256+ virtual devices)."""
    from repro.configs import get_config
    from repro.configs.base import INPUT_SHAPES
    from repro.data.pipeline import batch_spec
    from repro.launch import inputs as I
    from repro.launch.mesh import make_production_mesh
    from repro.train.trainer import make_train_step

    cfg = get_config("olmo-1b")
    shape = INPUT_SHAPES["train_4k"]
    mesh = make_production_mesh()
    for progress in ("global", "per_vci", "hybrid"):
        step = make_train_step(cfg, mesh=mesh, comm="vci", num_streams=8,
                               num_vcis=8, progress=progress)
        with jax.set_mesh(mesh):
            jax.jit(step).lower(I.train_state_struct(cfg),
                                batch_spec(cfg, shape, mesh)).compile()


def check_flash_decode_sequence_sharded():
    """partial_attention + combine_partials (flash-decode LSE combine) over
    a sequence-sharded KV cache == single-device decode_attention — the
    long-context decode path where the cache is the only shardable state."""
    from repro.configs import get_config
    from repro.models.attention import (KVCache, combine_partials,
                                        decode_attention, partial_attention)

    mesh = _mesh1d()
    n = mesh.size
    cfg = get_config("yi-9b-smoke")
    b, s, kv, hd = 2, 64, cfg.num_kv_heads, cfg.head_dim
    h = cfg.num_heads
    assert s % n == 0
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    length = 50  # only the first 50 slots are valid

    # reference: full-cache decode attention
    cache = KVCache(kc, vc, jnp.asarray(length, jnp.int32), False)
    ref = decode_attention(cfg, q, cache)

    # distributed: sequence shards + LSE combine across the mesh
    def shard_attn(q, kcs, vcs, start):
        idx = start[0] + jnp.arange(kcs.shape[1])
        valid = idx < length
        out, m, l = partial_attention(q, kcs, vcs, valid)
        outs = jax.lax.all_gather(out, "data")            # (n,B,1,H,hd)
        ms = jax.lax.all_gather(m, "data")                # (n,B,H,1,1)
        ls = jax.lax.all_gather(l, "data")
        return combine_partials(outs, ms, ls)

    starts = jnp.arange(n, dtype=jnp.int32)[:, None] * (s // n)
    f = jax.jit(jax.shard_map(
        shard_attn, mesh=mesh,
        in_specs=(P(), P(None, "data"), P(None, "data"), P("data")),
        out_specs=P(), check_vma=False))
    got = f(q, kc, vc, starts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


CHECKS = {k[len("check_"):]: v for k, v in list(globals().items())
          if k.startswith("check_")}


def main():
    names = sys.argv[1:] or list(CHECKS)
    failed = 0
    for name in names:
        try:
            CHECKS[name]()
            print(f"PASS {name}", flush=True)
        except Exception:
            failed += 1
            print(f"FAIL {name}:\n{traceback.format_exc()}", flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
