#!/usr/bin/env python3
"""Smoke run of the serve and train paths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip paths, and nothing else

One chip:

* serve: olmo-1b at its published widths and full depth through
  ``ServeEngine.generate`` on the paged KV cache, batch 4 and 8 requests so
  that finished slots take new requests mid-stream. The decode step must
  hold the Pallas paged attention kernel (``tpu_custom_call``); a full
  forward over each finished sequence must be finite and pick the same
  tokens wherever its top-2 margin is wider than ``LOGIT_BOUND``;
* train: olmo-1b at published widths cut to ``TRAIN_LAYERS`` layers, a few
  steps through ``make_train_step`` with ``comm="vci"`` (fused Pallas bucket
  pack/unpack, barrier ordering tokens) and then ``comm="gspmd"`` on the same
  batches; the losses must agree within ``LOSS_RTOL_1``.

Four chips (``--chips 4``):

* serve: the same olmo-1b tensor-parallel on a data-2 x model-2 mesh with
  VCI streams and the paged cache, against the one-device engine on the same
  requests: last-position prefill logits within ``LOGIT_BOUND``, and tokens
  identical up to the first position whose top-2 margin is within it;
* train: the depth-cut olmo-1b data-parallel over four chips with
  ``comm="vci"``, ZeRO-1 and overlap scheduling, against ``comm="gspmd"``;
  the losses must agree within ``LOSS_RTOL_4``.

Weights and data are random, made from ``SEED``. The times printed are those
of a smoke run, not a benchmark. Every check raises when it fails. The last
line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU the script
exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402  (after the path insert, like the imports below)
import jax.numpy as jnp  # noqa: E402

ARCH = "olmo-1b"
SEED = 0
SERVE_BATCH = 4
PROMPT_LENS = (128, 64, 128, 64, 64, 128, 64, 128)
MAX_NEW = 32
PAGE_SIZE = 16
MAX_LEN = 256
TRAIN_LAYERS = 4
TRAIN_BATCH = 8
TRAIN_SEQ = 256
TRAIN_STEPS = 5
# Logits of the random-weight olmo-1b are O(1) and come out of a bf16 matmul,
# whose rounding step near 4 is 2**-6; two programs that order their sums
# differently may land several such steps apart.
LOGIT_BOUND = 0.125
# The loss falls by about 1e-3 of itself over these steps, so a tolerance
# must sit well below that to see a wrong update. One chip: the vci and gspmd
# steps compute the same math on one device.
LOSS_RTOL_1 = 2e-4
# Four chips: ZeRO-1 also keeps an f32 master copy where the replicated AdamW
# of the gspmd step rounds every update into the bf16 params.
LOSS_RTOL_4 = 5e-4

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the XLA compile time JAX reports (persistent-cache hits are
    loads and do not count)."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.secs += secs


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    say(f"check {'PASS' if ok else 'FAIL'}: {what}")
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def report_memory(tag: str) -> None:
    for d in jax.devices():
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        say(f"[{tag}] device {d.id} peak_bytes_in_use={peak}")


def report_shardings(tag: str, tree) -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        say(f"[{tag}] {jax.tree_util.keystr(path)} {tuple(leaf.shape)} "
            f"{leaf.sharding}")


def bytes_on(tree, device) -> tuple:
    """(bytes this device holds, bytes of the whole arrays)."""
    held = total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.size * leaf.dtype.itemsize
        for s in leaf.addressable_shards:
            if s.device == device:
                held += s.data.size * leaf.dtype.itemsize
    return held, total


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(cfg):
    from repro.serve.engine import Request

    rng = np.random.default_rng(SEED)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, (n,),
                                        dtype=np.int32),
                    max_new_tokens=MAX_NEW) for n in PROMPT_LENS]


def generate_twice(engine, reqs, clock: CompileClock, label: str):
    """Runs the requests twice; the first pass compiles, the second is the
    steady one. Returns the generated tokens."""
    from repro.serve.engine import Request

    passes = []
    for _ in range(2):
        fresh = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
                 for r in reqs]
        c0, t0 = clock.secs, time.perf_counter()
        engine.generate(fresh)
        passes.append((time.perf_counter() - t0, clock.secs - c0,
                       [r.generated for r in fresh]))
    (t_first, c_first, toks), (t_steady, c_steady, toks2) = passes
    n_tok = sum(len(t) for t in toks)
    say(f"[{label}] smoke timing, not a benchmark: compile_s={c_first:.2f} "
        f"first_pass_s={t_first:.2f} steady_pass_s={t_steady:.2f} "
        f"steady_compile_s={c_steady:.2f} tokens={n_tok}")
    check(all(len(t) == MAX_NEW for t in toks),
          f"{label}: every request returned its {MAX_NEW} tokens")
    check(all(np.array_equal(a, b) for a, b in zip(toks, toks2)),
          f"{label}: a second pass repeats every token")
    return toks


def left_pad(seqs):
    width = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), width), np.int32)
    start = np.zeros((len(seqs),), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, width - len(s):] = s
        start[i] = width - len(s)
    return tokens, start


def forward_logits(cfg, params, tokens, start):
    """One-device full forward of left-padded rows -> f32 logits."""
    from repro.models.transformer import Model

    fwd = jax.jit(lambda p, t, s: Model(cfg).forward(
        p, {"tokens": t}, start=s)[0].astype(jnp.float32))
    return np.asarray(fwd(params, tokens, start))


def teacher_forced(cfg, params, reqs, toks):
    """Logits of a full forward over prompt + generated tokens, and for each
    request the (argmax, top-2 margin) at every generated position."""
    seqs = [np.concatenate([r.prompt, t]) for r, t in zip(reqs, toks)]
    tokens, start = left_pad(seqs)
    logits = forward_logits(cfg, params, tokens, start)
    width = tokens.shape[1]
    picks = []
    for i, t in enumerate(toks):
        # the logits at position p predict the token at p + 1
        rows = logits[i, width - len(t) - 1: width - 1]
        top2 = np.sort(rows, axis=-1)[:, -2:]
        picks.append((rows.argmax(-1), top2[:, 1] - top2[:, 0]))
    return logits, start, picks


def check_against_forward(label, picks, toks) -> None:
    """Greedy decode must pick what a full forward picks wherever the
    forward's top-2 margin is wider than LOGIT_BOUND."""
    wide = agree = 0
    for (arg, margin), t in zip(picks, toks):
        sure = margin > LOGIT_BOUND
        wide += int(sure.sum())
        agree += int((arg[sure] == t[sure]).sum())
    say(f"[{label}] tokens with top-2 margin > {LOGIT_BOUND}: {wide}, "
        f"of which decode agrees with the full forward: {agree}")
    check(agree == wide, f"{label}: decode matches a full forward wherever "
                         f"the margin exceeds {LOGIT_BOUND}")


def check_same_greedy(label, picks, ref_toks, toks) -> None:
    """Two programs' greedy tokens must be identical up to each request's
    first difference, and that difference must sit where the full
    forward's top-2 margin (``picks`` of ``ref_toks``; the prefixes agree up
    to there) is within LOGIT_BOUND. Tokens after a difference follow a
    different prefix; they are only counted."""
    same = after = 0
    for i, ((_, margin), a, b) in enumerate(zip(picks, ref_toks, toks)):
        diff = np.nonzero(a != b)[0]
        if diff.size == 0:
            same += len(a)
            continue
        d = int(diff[0])
        same += d
        after += int((a[d + 1:] == b[d + 1:]).sum())
        say(f"[{label}] request {i} first differs at token {d}, where the "
            f"top-2 margin is {float(margin[d]):.4f}")
        check(float(margin[d]) <= LOGIT_BOUND,
              f"{label}: request {i} differs only where the margin is "
              f"within {LOGIT_BOUND}")
    say(f"[{label}] tokens identical before any difference: {same} of "
        f"{sum(len(a) for a in ref_toks)}; equal tokens after a "
        f"difference: {after}")


def serve_one_chip(cfg, clock: CompileClock) -> None:
    from repro.models.transformer import init_paged_cache, init_params
    from repro.serve.engine import ServeEngine

    say(f"[serve] {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}"
        f", {cfg.param_count() / 1e9:.2f}B params, {cfg.param_dtype} "
        f"weights; batch {SERVE_BATCH}, prompt lengths {PROMPT_LENS}, "
        f"{MAX_NEW} new tokens each")
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    reqs = make_requests(cfg)
    engine = ServeEngine(cfg, params, batch_size=SERVE_BATCH,
                         max_len=MAX_LEN, page_size=PAGE_SIZE)
    toks = generate_twice(engine, reqs, clock, "serve")
    check(len(engine._admit_fns) > 0,
          "serve: requests were admitted mid-stream")
    b = SERVE_BATCH
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, b, MAX_LEN, page_size=PAGE_SIZE,
        num_pages=engine._num_pages, dtype=engine._cache_dtype))
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32)
    hlo = engine._step.lower(
        params, jax.ShapeDtypeStruct((b, 1), jnp.int32), cache, i32,
        jax.ShapeDtypeStruct((b,), jnp.float32),
        jax.random.PRNGKey(0)).compile().as_text()
    n = hlo.count("tpu_custom_call")
    say(f"[serve] decode step: {n} tpu_custom_call mentions")
    check(n > 0, "serve: the decode step runs the Pallas paged attention "
                 "kernel")
    report_memory("serve")
    c0 = clock.secs
    logits, start, picks = teacher_forced(cfg, params, reqs, toks)
    live = np.arange(logits.shape[1])[None, :] >= start[:, None]
    check(bool(np.isfinite(logits[live]).all()),
          "serve: every logit of the full forward is finite")
    say(f"[serve forward] compile_s={clock.secs - c0:.2f}")
    check_against_forward("serve", picks, toks)


def serve_four_chips(cfg, clock: CompileClock) -> None:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models.transformer import Model, init_params
    from repro.serve.comm import ServeCommPlan, serve_param_specs
    from repro.serve.engine import ServeEngine

    devs = jax.devices()
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("data", "model"))
    tp = 2
    plan = ServeCommPlan(num_vcis=8)
    say(f"[serve tp] {cfg.name} full model on mesh data2 x model{tp}, "
        f"num_vcis=8, paged; against one device")
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    specs = serve_param_specs(cfg, params, tp)
    params_tp = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs))
    report_shardings("serve tp params", params_tp)
    held, total = bytes_on(params_tp, devs[0])
    say(f"[serve tp] device 0 holds {held} of {total} parameter bytes")
    check(held < total, "serve tp: the weights are split across the chips")

    reqs = make_requests(cfg)
    tokens, start = left_pad([r.prompt for r in reqs])

    def tp_inner(p, t, s):
        comm = plan.comm(0)
        logits, _, _ = Model(cfg, None, comm=comm).forward(
            p, {"tokens": t}, start=s)
        return comm.drain(logits)[:, -1].astype(jnp.float32)

    tp_last = jax.jit(jax.shard_map(
        tp_inner, mesh=mesh,
        in_specs=(specs, P("data", None), P("data")),
        out_specs=P("data", None), check_vma=False,
        axis_names=set(mesh.axis_names)))
    c0 = clock.secs
    got = np.asarray(tp_last(params_tp, tokens, start))
    ref = forward_logits(cfg, params, tokens, start)[:, -1]
    err = float(np.abs(got - ref).max())
    say(f"[serve tp] prefill compile_s={clock.secs - c0:.2f}; last-position "
        f"logits: max |tp - one device| = {err:.6f} (bound {LOGIT_BOUND}, "
        f"max |logit| {float(np.abs(ref).max()):.3f})")
    check(err <= LOGIT_BOUND, f"serve tp: prefill logits within "
                              f"{LOGIT_BOUND} of one device")

    one = ServeEngine(cfg, params, batch_size=SERVE_BATCH, max_len=MAX_LEN,
                      page_size=PAGE_SIZE)
    ref_toks = generate_twice(one, reqs, clock, "serve one device")
    eng = ServeEngine(cfg, params_tp, batch_size=SERVE_BATCH,
                      max_len=MAX_LEN, mesh=mesh, comm_plan=plan,
                      page_size=PAGE_SIZE)
    tp_toks = generate_twice(eng, reqs, clock, "serve tp")
    check(len(eng._admit_fns) > 0,
          "serve tp: requests were admitted mid-stream under the mesh")
    report_memory("serve tp")

    _, _, picks = teacher_forced(cfg, params, reqs, ref_toks)
    check_same_greedy("serve tp vs one device", picks, ref_toks, tp_toks)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_config():
    from repro.configs import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=TRAIN_LAYERS,
                              name=f"{ARCH}-{TRAIN_LAYERS}of"
                                   f"{full.num_layers}L")
    say(f"[train] depth cut: {cfg.name} keeps {TRAIN_LAYERS} of "
        f"{full.num_layers} layers at published widths, "
        f"{cfg.param_count() / 1e9:.3f}B of {full.param_count() / 1e9:.3f}B "
        f"params; batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, {TRAIN_STEPS} steps")
    return cfg


def run_steps(label, step, state, batches, clock: CompileClock):
    """Runs the jitted step over the batches; returns (state, losses)."""
    losses, norms = [], []
    c0, t0 = clock.secs, time.perf_counter()
    for i, batch in enumerate(batches):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        norms.append(float(m["grad_norm"]))
        if i == 0:
            t_first = time.perf_counter() - t0
            c_first = clock.secs - c0
            t0 = time.perf_counter()
    steady = (time.perf_counter() - t0) / max(1, len(batches) - 1)
    say(f"[{label}] smoke timing, not a benchmark: compile_s={c_first:.2f} "
        f"first_step_s={t_first:.2f} steady_step_s={steady:.4f} "
        f"steady_compile_s={clock.secs - c0 - c_first:.2f}")
    say(f"[{label}] losses {['%.6f' % x for x in losses]} grad_norms "
        f"{['%.6f' % x for x in norms]}")
    check(bool(np.isfinite(losses).all() and np.isfinite(norms).all()),
          f"{label}: losses and grad norms are finite")
    return state, losses


def compare_losses(label, a, b, rtol) -> None:
    worst = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    say(f"[{label}] max relative loss difference {worst:.3e} (rtol {rtol})")
    check(worst <= rtol, f"{label}: losses agree within rtol {rtol}")


def train_one_chip(clock: CompileClock) -> None:
    from jax.sharding import Mesh

    from repro.data.pipeline import synthetic_batch
    from repro.train.trainer import make_train_step, train_state_init

    cfg = train_config()
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    batches = [synthetic_batch(cfg, TRAIN_BATCH, TRAIN_SEQ, seed=SEED,
                               step=i) for i in range(TRAIN_STEPS)]
    key = jax.random.PRNGKey(SEED)

    # The steps' outputs are committed to the chip; an uncommitted first
    # state would make jit compile each step a second time on step 2.
    dev = jax.devices()[0]
    vci = jax.jit(make_train_step(cfg, mesh=mesh, comm="vci", pack="pallas",
                                  token_impl="barrier"))
    state = jax.device_put(train_state_init(cfg, key, mesh=mesh,
                                            pack="pallas"), dev)
    lowered = vci.lower(state, batches[0])
    check("optimization_barrier" in lowered.as_text(),
          "train vci: ordering tokens are optimization barriers")
    state, loss_v = run_steps("train vci", vci, state, batches, clock)
    n = lowered.compile().as_text().count("tpu_custom_call")
    say(f"[train vci] step: {n} tpu_custom_call mentions")
    check(n >= 2, "train vci: the step runs the Pallas bucket pack and "
                  "unpack kernels")
    report_memory("train vci")
    del state

    gspmd = jax.jit(make_train_step(cfg, mesh=mesh, comm="gspmd"))
    state = jax.device_put(train_state_init(cfg, key), dev)
    state, loss_g = run_steps("train gspmd", gspmd, state, batches, clock)
    report_memory("train gspmd")
    compare_losses("train vci vs gspmd", loss_v, loss_g, LOSS_RTOL_1)


def train_four_chips(clock: CompileClock) -> None:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.data.pipeline import synthetic_batch
    from repro.dist.sharding import zero1_opt_specs
    from repro.train.trainer import TrainState, make_train_step, \
        train_state_init

    cfg = train_config()
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("data",))
    say(f"[train dp] data-parallel over {len(devs)} chips: vci + zero1 + "
        f"overlap against gspmd")
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    batches = [jax.device_put(synthetic_batch(cfg, TRAIN_BATCH, TRAIN_SEQ,
                                              seed=SEED, step=i), data)
               for i in range(TRAIN_STEPS)]
    key = jax.random.PRNGKey(SEED)

    knobs = dict(optimizer="zero1", schedule="overlap")
    z1 = make_train_step(cfg, mesh=mesh, comm="vci", token_impl="barrier",
                         **knobs)
    state = train_state_init(cfg, key, mesh=mesh, **knobs)
    shard = TrainState(
        params=jax.tree_util.tree_map(lambda _: rep, state.params),
        opt=jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                   zero1_opt_specs(mesh, state.opt)),
        step=rep)
    state = jax.device_put(state, shard)
    report_shardings("train zero1 opt", state.opt)
    held, total = bytes_on(state.opt, devs[0])
    say(f"[train zero1] device 0 holds {held} of {total} optimizer bytes")
    check(held * len(devs) <= total * 1.01,
          "train zero1: each chip holds a quarter of the optimizer state")
    with jax.set_mesh(mesh):
        step = jax.jit(z1, in_shardings=(shard, data),
                       out_shardings=(shard, None))
        state, loss_z = run_steps("train zero1 overlap", step, state,
                                  batches, clock)
    report_memory("train zero1 overlap")
    del state

    state = train_state_init(cfg, key)
    shard_g = jax.tree_util.tree_map(lambda _: rep, state)
    state = jax.device_put(state, shard_g)
    with jax.set_mesh(mesh):
        step = jax.jit(make_train_step(cfg, mesh=mesh, comm="gspmd"),
                       in_shardings=(shard_g, data),
                       out_shardings=(shard_g, None))
        state, loss_g = run_steps("train gspmd", step, state, batches,
                                  clock)
    report_memory("train gspmd")
    compare_losses("train zero1 overlap vs gspmd", loss_z, loss_g,
                   LOSS_RTOL_4)


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + train on one chip; 4: only the "
                         "multi-chip paths and what they are compared with")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{dev.platform!r}); this script runs on a TPU only",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    say(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    if args.chips == 1:
        serve_one_chip(cfg, clock)
        train_one_chip(clock)
    else:
        serve_four_chips(cfg, clock)
        train_four_chips(clock)
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
