"""Train-step builders.

Two gradient-communication modes:

* ``comm="gspmd"`` (production default, used by the dry-run): parameters are
  FSDP(data/pod) x TP(model) sharded; XLA inserts the gradient
  reduce-scatters/all-gathers from the sharding constraints.

* ``comm="vci"`` (the paper's mode): the step runs under ``shard_map`` with
  the data axes MANUAL and the model axis auto (GSPMD). Parameters are
  replicated over data (DDP); gradients are explicitly partitioned into
  buckets, each bucket assigned a CommContext -> VCI, and reduced on
  independent streams by :func:`repro.core.bucketing.reduce_gradients`.
  ``progress`` / ``num_streams`` / ``vci_policy`` / ``token_impl`` expose the
  paper's entire design space (Global vs FG vs per-VCI, Fig. 5-8 ablations).

  Fast-path knobs (this repo's §4.3 per-VCI-request-cache analogue; see the
  knob matrix in ``repro.core.bucketing``):

  * ``persistent_plan`` — cache the BucketPlan/CommWorld/contexts/pack
    tables across steps and retraces (True; False = seed per-step rebuild);
  * ``pack="xla"|"pallas"``   — concat-chain vs arena + fused tile-gather
    pack/unpack kernels (``repro.kernels.bucket_pack``);
  * ``reduction="all_reduce"|"reduce_scatter"`` — full all-reduce vs
    per-bucket reduce_scatter + all_gather (half the wire bytes for DDP).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import TILE, get_comm_plan, reduce_gradients
from repro.core.bucketing import (ShardLayout, all_gather_shards,
                                  overlap_boundaries, plan_buckets)
from repro.dist.sharding import Sharder, batch_axes, dp_entry, zero1_opt_specs
from repro.models.transformer import Model, init_params
from repro.optim.adamw import (adamw_init, adamw_update,
                               bucket_decay_masks, sharded_adamw_init,
                               sharded_adamw_update)
from repro.train.losses import total_loss


class TrainState(NamedTuple):
    params: Any
    opt: Any                     # AdamWState | ShardedAdamWState (zero1)
    step: jax.Array


def _zero1_plan(params_or_grads, *, num_streams: int, align: int, pack: str,
                schedule: str = "post"):
    """The bucket plan the zero1 path uses — MUST match what the step's
    ``get_comm_plan`` builds, so state init and update agree on layout.
    ``schedule="overlap"`` plans use-order-contiguous buckets (the
    bucket-ready layout), so the flat state layout differs from ``"post"``
    and state must be initialized with the matching schedule."""
    slot_align = align if pack == "pallas" else None
    return plan_buckets(params_or_grads, num_streams, align=align,
                        slot_align=slot_align,
                        partition="contig" if schedule == "overlap"
                        else "size")


def train_state_init(cfg: ModelConfig, key: jax.Array, *,
                     optimizer: str = "replicated",
                     mesh=None, num_streams: int = 8,
                     bucket_align: int = TILE,
                     pack: str = "xla",
                     schedule: str = "post") -> TrainState:
    """Fresh params + optimizer state.

    ``optimizer="zero1"`` builds the ZeRO-1 flat-bucket state
    (:func:`sharded_adamw_init`): pass the SAME ``mesh`` / ``num_streams`` /
    ``bucket_align`` / ``pack`` / ``schedule`` the matching
    ``make_train_step`` gets, since the bucket plan (and therefore every
    buffer's layout) derives from them.
    """
    params = init_params(cfg, key)
    if optimizer == "replicated":
        opt = adamw_init(params, moment_dtype=jnp.dtype(cfg.optimizer_dtype))
    elif optimizer == "zero1":
        if mesh is None:
            raise ValueError("optimizer='zero1' needs a mesh (the data axes "
                             "define the shard layout)")
        plan = _zero1_plan(params, num_streams=num_streams,
                           align=bucket_align, pack=pack, schedule=schedule)
        n = 1
        for a in batch_axes(mesh):
            n *= dict(mesh.shape)[a]
        ShardLayout(plan, n)  # validate divisibility up front
        opt = sharded_adamw_init(params, plan,
                                 moment_dtype=jnp.dtype(cfg.optimizer_dtype))
    else:
        raise ValueError(f"unknown optimizer {optimizer!r}")
    return TrainState(params, opt, jnp.zeros((), jnp.int32))


def _loss_fn(model: Model, cfg: ModelConfig, params, batch):
    logits, aux, _ = model.forward(params, batch)
    loss, metrics = total_loss(cfg, logits, batch["labels"], aux)
    return loss, metrics


def make_train_step(
    cfg: ModelConfig,
    *,
    mesh: Optional[Mesh] = None,
    lr_fn: Optional[Callable] = None,
    comm: str = "gspmd",
    accum_steps: int = 1,
    # --- vci-mode knobs (paper §4/§5) ---
    num_streams: int = 8,
    num_vcis: int = 8,
    vci_policy: str = "fcfs",
    progress: str = "hybrid",
    join_every: int = 8,
    token_impl: str = "barrier",
    staging: str = "per_vci",
    bucket_align: int = 8 * 128,
    # --- fast-path knobs (persistent plans + fused pack, see bucketing) ---
    pack: str = "xla",
    reduction: str = "all_reduce",
    persistent_plan: bool = True,
    max_grad_norm: Optional[float] = 1.0,
    # --- optimizer layout (ZeRO-1) ---
    optimizer: str = "replicated",
    zero1_wire_dtype: Optional[str] = None,
    # --- comm schedule (bucket-ready overlap) ---
    schedule: str = "post",
) -> Callable[[TrainState, Any], tuple]:
    """Returns ``train_step(state, batch) -> (state, metrics)``.

    The returned function is NOT jitted; callers jit with the appropriate
    in/out shardings (launch/train.py) or call it inside tests directly.

    ``optimizer`` selects the optimizer layout (vci mode only):

    * ``"replicated"`` — every rank reduces the full gradient tree and
      applies the full AdamW update (DDP).
    * ``"zero1"`` — ZeRO-1: per-bucket ``reduce_scatter`` hands each rank
      only its :class:`ShardLayout` shard, :func:`sharded_adamw_update`
      updates m/v and the fp32 master copy for that shard alone, and the
      *updated params* are all-gathered once per bucket on the SAME
      CommContext/VCI the reduce used. Gradient wire bytes are halved
      (scatter only, no gradient gather) and optimizer memory drops 1/N.
      State must come from ``train_state_init(optimizer="zero1")`` with
      matching mesh/num_streams/bucket_align/pack/schedule.
      ``zero1_wire_dtype`` (e.g. ``"bfloat16"``) sets the payload dtype of
      BOTH the gradient scatter and the param gather — the mixed-precision
      deployment recipe (fp32 master shards absorb the wire rounding);
      ``None`` keeps f32 wire, which matches the replicated path to fp32
      tolerance.

    ``schedule`` selects WHEN gradient reduction happens (vci mode only):

    * ``"post"`` — the classic post-pass: the full backward finishes, then
      every bucket is packed and reduced.
    * ``"overlap"`` — bucket-ready overlap
      (:func:`repro.core.bucketing.overlap_boundaries`): each bucket's
      reduce is issued on its VCI stream *inside the backward*, the moment
      its cotangents exist, so communication runs concurrently with the
      remaining backward compute (same wire bytes, shorter critical path).
      With microbatch accumulation only the LAST microbatch's backward
      carries the boundaries — earlier microbatches accumulate locally and
      their sum rides into the boundary as a carry, so reduces are issued
      once per step, not per microbatch. With ``optimizer="zero1"`` the
      per-bucket sharded-AdamW update and updated-param all_gather are
      additionally issued in backward ready order
      (``CommPlan.ready_order``), pipelining the gather latency behind
      later buckets' reduces.
    """
    if optimizer not in ("replicated", "zero1"):
        raise ValueError(f"unknown optimizer {optimizer!r}")
    if optimizer == "zero1" and comm != "vci":
        raise ValueError("optimizer='zero1' requires comm='vci' (the "
                         "bucketed reduce_scatter path)")
    if schedule not in ("post", "overlap"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "overlap" and comm != "vci":
        raise ValueError("schedule='overlap' requires comm='vci' (the "
                         "bucketed reduction path)")
    if schedule == "overlap" and staging != "per_vci":
        raise ValueError("schedule='overlap' requires staging='per_vci': "
                         "shared staging threads one buffer through every "
                         "bucket, which re-serializes the backward-issued "
                         "reduces it exists to overlap")
    if lr_fn is None:
        lr_fn = lambda step: 3e-4
    shard = Sharder(mesh, cfg) if (mesh is not None and comm == "gspmd") else (
        Sharder(None, cfg))
    model = Model(cfg, shard if mesh is not None and comm == "gspmd" else None)

    def _mb_split(batch):
        """Split the batch dim into ``accum_steps`` leading microbatches."""
        def split(x):
            b = x.shape[0]
            assert b % accum_steps == 0, (b, accum_steps)
            return x.reshape((accum_steps, b // accum_steps) + x.shape[1:])
        return jax.tree_util.tree_map(split, batch)

    def _mb_zero_acc(params, mb):
        """(zero f32 grad acc, zero metric acc) for the scan carry."""
        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        _, m0 = jax.eval_shape(
            functools.partial(_loss_fn, model, cfg), params,
            jax.tree_util.tree_map(lambda x: x[0], mb))
        zero_m = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape),
            m0[1] if isinstance(m0, tuple) else m0)
        return zero_g, zero_m

    def _mb_body(params):
        def body(carry, microbatch):
            acc_g, acc_m = carry
            (_, metrics), grads = jax.value_and_grad(
                functools.partial(_loss_fn, model, cfg), has_aux=True)(
                    params, microbatch)
            acc_g = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) / accum_steps,
                acc_g, grads)
            acc_m = jax.tree_util.tree_map(
                lambda a, m: a + m / accum_steps, acc_m, metrics)
            return (acc_g, acc_m), None
        return body

    def grads_and_metrics(params, batch):
        if accum_steps == 1:
            (_, metrics), grads = jax.value_and_grad(
                functools.partial(_loss_fn, model, cfg), has_aux=True)(
                    params, batch)
            return grads, metrics
        # microbatch accumulation: split the batch dim, scan, mean grads
        mb = _mb_split(batch)
        (grads, metrics), _ = jax.lax.scan(
            _mb_body(params), _mb_zero_acc(params, mb), mb)
        grads = jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), grads, params)
        return grads, metrics

    def overlap_grads_and_metrics(params, batch, loss_with_boundaries):
        """Backward with bucket boundaries: ``loss_with_boundaries(params,
        microbatch, carry) -> (metrics, grads_or_shards)`` must wrap params
        via :func:`overlap_boundaries`. Only the LAST microbatch runs with
        the boundaries (triggering the reduces); earlier microbatches
        accumulate locally and ride in as the carry."""
        if accum_steps == 1:
            return loss_with_boundaries(params, batch, None)
        mb = _mb_split(batch)
        prefix = jax.tree_util.tree_map(lambda x: x[:accum_steps - 1], mb)
        last = jax.tree_util.tree_map(lambda x: x[accum_steps - 1], mb)
        (acc_g, acc_m), _ = jax.lax.scan(
            _mb_body(params), _mb_zero_acc(params, mb), prefix)
        carry = jax.lax.stop_gradient(acc_g)
        metrics_last, out = loss_with_boundaries(params, last, carry)
        metrics = jax.tree_util.tree_map(
            lambda a, m: a + m / accum_steps, acc_m, metrics_last)
        return metrics, out

    def apply_update(state: TrainState, grads, metrics):
        lr = lr_fn(state.step)
        new_p, new_opt, om = adamw_update(
            grads, state.opt, state.params, lr=jnp.asarray(lr, jnp.float32),
            max_grad_norm=max_grad_norm)
        metrics = dict(metrics) | om | {"lr": jnp.asarray(lr, jnp.float32)}
        return TrainState(new_p, new_opt, state.step + 1), metrics

    if comm == "gspmd":
        def train_step(state: TrainState, batch):
            grads, metrics = grads_and_metrics(state.params, batch)
            return apply_update(state, grads, metrics)
        return train_step

    if comm != "vci":
        raise ValueError(f"unknown comm mode {comm!r}")

    # ---------------- vci mode -------------------------------------------
    assert mesh is not None, "vci mode needs a mesh"
    dp = batch_axes(mesh)
    n_data = 1
    for a in dp:
        n_data *= dict(mesh.shape)[a]
    wire = jnp.dtype(zero1_wire_dtype) if zero1_wire_dtype else jnp.float32

    def _comm_plan(grads):
        # Persistent plan: BucketPlan + CommWorld + contexts + pack tables
        # are cached on (treedef, shapes, knobs) — rebuilt per call only in
        # the per-step ablation mode. The CommRuntime (ordering tokens) is
        # trace-local and minted fresh either way.
        return get_comm_plan(grads, num_streams=num_streams,
                             align=bucket_align, pack=pack, num_vcis=num_vcis,
                             vci_policy=vci_policy, progress=progress,
                             join_every=join_every, token_impl=token_impl,
                             schedule=schedule, persistent=persistent_plan)

    def inner_step(state: TrainState, batch):
        grads, metrics = grads_and_metrics(state.params, batch)
        cp = _comm_plan(grads)
        grads = reduce_gradients(cp.runtime(), grads, cp, axis=dp, mean=True,
                                 staging=staging, pack=pack,
                                 reduction=reduction)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp), metrics)
        return apply_update(state, grads, metrics)

    def inner_step_overlap(state: TrainState, batch):
        # The reduces live INSIDE the backward: each bucket's custom_vjp
        # boundary issues its reduce on its VCI stream as soon as that
        # bucket's cotangents exist, so value_and_grad returns the
        # already-reduced mean gradients and there is no post-pass.
        cp = _comm_plan(state.params)

        def run_last(params, microbatch, carry):
            def loss_w(p, b):
                wp = overlap_boundaries(cp, p, axis=dp, carry=carry,
                                        accum_steps=accum_steps, mean=True,
                                        pack=pack, reduction=reduction)
                return _loss_fn(model, cfg, wp, b)
            (_, metrics), grads = jax.value_and_grad(
                loss_w, has_aux=True)(params, microbatch)
            return metrics, grads

        metrics, grads = overlap_grads_and_metrics(
            state.params, batch, run_last)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp), metrics)
        return apply_update(state, grads, metrics)

    def inner_step_zero1(state: TrainState, batch, mask_shards):
        grads, metrics = grads_and_metrics(state.params, batch)
        cp = _comm_plan(grads)
        rt = cp.runtime()
        # 1) scatter: each rank receives (and owns) 1/N of every bucket.
        shards, layout = reduce_gradients(
            rt, grads, cp, axis=dp, mean=True, staging=staging, pack=pack,
            reduction="reduce_scatter", output="shards", reduce_dtype=wire)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp), metrics)
        # 2) local AdamW on the owned shards (norm partials psum'd on the
        # first bucket's context). mask_shards arrived pre-sliced to this
        # rank's window by the P(data) in_spec.
        lr = lr_fn(state.step)
        new_shards, new_opt, om = sharded_adamw_update(
            shards, state.opt, lr=jnp.asarray(lr, jnp.float32),
            layout=layout, decay_masks=mask_shards,
            psum=lambda s: rt.all_reduce(s, cp.contexts[0], axis=dp),
            max_grad_norm=max_grad_norm)
        # 3) gather the UPDATED PARAMS per bucket on the reduce's VCI.
        new_params = all_gather_shards(rt, new_shards, cp, axis=dp,
                                       wire_dtype=wire)
        metrics = dict(metrics) | om | {"lr": jnp.asarray(lr, jnp.float32)}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    def inner_step_zero1_overlap(state: TrainState, batch, mask_shards):
        # ZeRO-1 overlap: the backward's bucket boundaries reduce_scatter
        # each bucket the moment its cotangents exist; the shards leave the
        # backward as the taps' gradients (cotangent shapes must match
        # their primals, so the 1/N shards ride a zero-initialized side
        # input instead of the params). The sharded-AdamW update and the
        # updated-param all_gather are then issued in backward READY order.
        # NOTE: with the default global-norm clip, every update depends on
        # the clip scale and therefore on the LAST scatter — the win is the
        # scatters overlapping the backward; gathers pipeline ahead of
        # later gathers only, or fully (behind still-running reduces) when
        # max_grad_norm=None removes the clip barrier.
        cp = _comm_plan(state.params)
        rt = cp.runtime()
        layout = ShardLayout(cp.plan, n_data)
        taps = tuple(jnp.zeros((s,), jnp.float32) for s in layout.shard_sizes)

        def run_last(params, microbatch, carry):
            def loss_w(p, t, b):
                wp = overlap_boundaries(cp, p, axis=dp, taps=t, carry=carry,
                                        accum_steps=accum_steps, mean=True,
                                        pack=pack, reduce_dtype=wire)
                return _loss_fn(model, cfg, wp, b)
            (_, metrics), (_, shards) = jax.value_and_grad(
                loss_w, argnums=(0, 1), has_aux=True)(
                    params, taps, microbatch)
            return metrics, shards

        metrics, shards = overlap_grads_and_metrics(
            state.params, batch, run_last)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp), metrics)
        lr = lr_fn(state.step)
        new_shards, new_opt, om = sharded_adamw_update(
            list(shards), state.opt, lr=jnp.asarray(lr, jnp.float32),
            layout=layout, decay_masks=mask_shards,
            psum=lambda s: rt.all_reduce(s, cp.contexts[0], axis=dp),
            max_grad_norm=max_grad_norm, bucket_order=cp.ready_order)
        new_params = all_gather_shards(rt, new_shards, cp, axis=dp,
                                       wire_dtype=wire,
                                       order=cp.ready_order)
        metrics = dict(metrics) | om | {"lr": jnp.asarray(lr, jnp.float32)}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    METRIC_KEYS = ("ce", "tokens", "load_balance", "router_z", "loss",
                   "grad_norm", "lr")

    def train_step(state: TrainState, batch):
        batch_spec = jax.tree_util.tree_map(lambda _: P(dp), batch)
        metric_specs = {k: P() for k in METRIC_KEYS}
        if optimizer == "zero1":
            # flat m/v/master buffers live SHARDED on the data axes; params
            # and the step count replicate (dist.sharding.zero1_opt_specs).
            state_spec = TrainState(
                params=jax.tree_util.tree_map(lambda _: P(), state.params),
                opt=zero1_opt_specs(mesh, state.opt),
                step=P())
            # decay masks ride in P(data)-spec'd like the opt buffers, so
            # each rank stores only its shard of the full-bucket masks
            # (grads share the params' shapes, hence the same plan).
            plan = _zero1_plan(state.params, num_streams=num_streams,
                               align=bucket_align, pack=pack,
                               schedule=schedule)
            masks = tuple(jnp.asarray(m) for m in bucket_decay_masks(plan))
            dpe = dp_entry(dp)
            step_z1 = (inner_step_zero1_overlap if schedule == "overlap"
                       else inner_step_zero1)
            f = jax.shard_map(step_z1, mesh=mesh,
                              in_specs=(state_spec, batch_spec,
                                        tuple(P(dpe) for _ in masks)),
                              out_specs=(state_spec, metric_specs),
                              check_vma=False, axis_names=set(dp))
            return f(state, batch, masks)
        state_spec = jax.tree_util.tree_map(lambda _: P(), state)
        step_rep = inner_step_overlap if schedule == "overlap" else inner_step
        f = jax.shard_map(step_rep, mesh=mesh,
                          in_specs=(state_spec, batch_spec),
                          out_specs=(state_spec, metric_specs),
                          check_vma=False, axis_names=set(dp))
        return f(state, batch)

    return train_step
