#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e (``v5e:2x2``) on a
machine without one, and print what ``memory_analysis()`` gives per chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [cell ...]

Nothing runs: the TPU compiler refuses here what it would refuse on the
chip (a program that does not fit, a kernel it cannot lower), and the
bytes it plans per chip are set beside the chip's measured peak. Code that
asks for the backend still sees the CPU, so the page gather is steered to
its TPU kernel here.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, traffic  # noqa: E402
from chipbench.reference import family  # noqa: E402

GB = 1e9


def report(label, compiled):
    m = compiled.memory_analysis()
    args, out, tmp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                      m.temp_size_in_bytes)
    alias = getattr(m, "alias_size_in_bytes", 0)
    print(f"{label}: arguments {args / GB:.3f} GB, outputs {out / GB:.3f} GB"
          f" (aliased {alias / GB:.3f}), temporaries {tmp / GB:.3f} GB, "
          f"per chip {(args + out - alias + tmp) / GB:.3f} GB; "
          f"tpu_custom_call {compiled.as_text().count('tpu_custom_call')}",
          flush=True)


def shapes_on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def serve(cell, topo):
    from jax.sharding import SingleDeviceSharding
    import repro.kernels.paged_kv as paged_kv
    from repro.models.transformer import init_paged_cache
    from repro.serve.engine import ServeEngine

    paged_kv._on_tpu = lambda: True
    one = SingleDeviceSharding(topo.devices[0])
    cfg = harness.model_config(cell.config)
    ref = family(cell.config["reference"])
    params = shapes_on(jax.eval_shape(
        lambda k: ref.init_params(cell.config, k), jax.random.key(0)), one)
    e = cell.settings["engine"]
    eng = ServeEngine(cfg, params, **e)
    b = e["batch_size"]
    cache = shapes_on(jax.eval_shape(lambda: init_paged_cache(
        cfg, b, e["max_len"], page_size=e["page_size"],
        num_pages=eng._num_pages, dtype=eng._cache_dtype)), one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    key = shapes_on(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one)
    report(f"{cell.name} decode step", eng._step.lower(
        params, i32(b, 1), cache, i32(b), f32(b), key).compile())
    for w in traffic.prompt_lengths(cell.traffic):
        report(f"{cell.name} prefill {b} x {w}", eng._prefill.lower(
            params, {"tokens": i32(b, w)}, cache, i32(b), f32(b),
            key).compile())
        report(f"{cell.name} admission 1 x {w}", eng._admit_fn(w).lower(
            params, i32(1, w), cache, i32(), i32(), i32(1), f32(1),
            key).compile())


def train(cell, topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.dist.sharding import zero1_opt_specs
    from repro.optim.adamw import adamw_init, sharded_adamw_init
    from repro.train.trainer import TrainState, _zero1_plan, make_train_step

    cfg = harness.model_config(cell.config)
    ref = family(cell.config["reference"])
    knobs = dict(cell.settings["step"])
    mesh = Mesh(np.array(topo.devices[: cell.chips]), ("data",))
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    pshape = jax.eval_shape(lambda k: ref.init_params(cell.config, k),
                            jax.random.key(0))
    if knobs.get("optimizer") == "zero1":
        plan = _zero1_plan(pshape, num_streams=8, align=8 * 128,
                           pack=knobs.get("pack", "xla"),
                           schedule=knobs.get("schedule", "post"))
        oshape = jax.eval_shape(lambda p: sharded_adamw_init(p, plan), pshape)
        osh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                     zero1_opt_specs(mesh, oshape))
    else:
        oshape = jax.eval_shape(adamw_init, pshape)
        osh = jax.tree_util.tree_map(lambda _: rep, oshape)
    shard = TrainState(jax.tree_util.tree_map(lambda _: rep, pshape), osh,
                       rep)
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        TrainState(pshape, oshape, jax.ShapeDtypeStruct((), jnp.int32)),
        shard)
    t = cell.traffic
    tok = jax.ShapeDtypeStruct((t["global_batch"], t["seq_len"]), jnp.int32,
                               sharding=data)
    use_mesh = mesh if (knobs.get("comm") == "vci" or cell.chips > 1) \
        else None
    fn = make_train_step(cfg, mesh=use_mesh, **knobs)
    with jax.set_mesh(mesh):
        step = jax.jit(fn, in_shardings=(shard, data),
                       out_shardings=(shard, None), donate_argnums=(0,))
        report(f"{cell.name} train step",
               step.lower(state, {"tokens": tok, "labels": tok}).compile())


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = harness.benchmark()
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = harness.load_cell(name, listed=False)
        {"serve": serve, "train": train}[cell.entry](cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
