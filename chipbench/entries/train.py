"""Training cells: the jitted ``make_train_step`` step over the cell's
batches, with the state donated.

Set-up makes the weights on the device from the seed, builds the state in
the layout the cell's optimizer needs, compiles the step, and drives that
same step and state through the first ``check.steps`` steps: those are the
steps the reference follows. It records each step's loss, each leaf's norm
of the first gradient as the optimizer received it (read back from the
first moment after one step) and, after the last of them, each leaf's norm
of the change of the parameters. The window then runs further steps of the
same object until ``--seconds`` have passed; every step ends in
``block_until_ready``.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import traffic as traffic_mod
from chipbench.harness import Compared, jax_key, model_config, say
from chipbench.reference import adamw as ref_adamw
from chipbench.reference import family


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree_util.tree_leaves(tree)])


def gap(prog, ref, keep):
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median kept leaf."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    base = np.maximum(ref, np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / base[keep]))


class Run:
    def __init__(self, cell, seed: int, devs):
        self.cell, self.seed, self.devs = cell, seed, devs
        self.cfg_dict = cell.config
        self.cfg = model_config(cell.config)
        self.knobs = dict(cell.settings["step"])
        self.hp = cell.settings["optimizer"]
        self.check_cfg = cell.settings["check"]
        self.traffic = cell.traffic
        self.tokens_per_step = (self.traffic["global_batch"]
                                * self.traffic["seq_len"])

    # -- set-up ------------------------------------------------------------
    def _shardings(self, params):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.mesh = Mesh(np.array(self.devs), ("data",))
        rep = NamedSharding(self.mesh, P())
        self.data = NamedSharding(self.mesh, P("data"))
        self.param_sh = jax.tree_util.tree_map(lambda _: rep, params)
        return rep

    def init_state(self, params):
        """(state, its shardings) in the layout the cell's optimizer needs,
        from the benchmark's weights."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.dist.sharding import zero1_opt_specs
        from repro.optim.adamw import adamw_init, sharded_adamw_init
        from repro.train.trainer import TrainState, _zero1_plan

        rep = NamedSharding(self.mesh, jax.sharding.PartitionSpec())
        if self.knobs.get("optimizer", "replicated") == "zero1":
            self.plan = _zero1_plan(
                params, num_streams=self.knobs.get("num_streams", 8),
                align=self.knobs.get("bucket_align", 8 * 128),
                pack=self.knobs.get("pack", "xla"),
                schedule=self.knobs.get("schedule", "post"))
            opt_shape = jax.eval_shape(
                lambda p: sharded_adamw_init(p, self.plan), params)
            opt_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                zero1_opt_specs(self.mesh, opt_shape))
            make_opt = lambda p: sharded_adamw_init(p, self.plan)
        else:
            self.plan = None
            opt_shape = jax.eval_shape(adamw_init, params)
            opt_sh = jax.tree_util.tree_map(lambda _: rep, opt_shape)
            make_opt = lambda p: adamw_init(
                p, moment_dtype=jnp.dtype(self.cfg.optimizer_dtype))
        shard = TrainState(params=self.param_sh, opt=opt_sh, step=rep)
        state = jax.jit(
            lambda p: TrainState(p, make_opt(p), jnp.zeros((), jnp.int32)),
            out_shardings=shard)(params)
        return state, shard

    def first_grad_norms(self, opt):
        """Each leaf's norm of the gradient the optimizer received on step
        one: its first moment is then (1 - b1) times that gradient."""
        import jax
        import jax.numpy as jnp

        if self.plan is None:
            return leaf_norms(opt.m) / (1.0 - self.hp["b1"])
        out = [None] * self.plan.num_leaves
        for b in self.plan.buckets:
            for s in b.slots:
                piece = jax.lax.slice_in_dim(opt.m[b.bid], s.offset,
                                             s.offset + s.size)
                out[s.index] = jnp.sqrt(jnp.sum(jnp.square(piece)))
        return jnp.stack(out) / (1.0 - self.hp["b1"])

    def setup(self) -> None:
        import jax
        from repro.train.trainer import make_train_step

        ref = family(self.cfg_dict["reference"])
        key = jax_key(self.seed)
        init = lambda k: ref.init_params(self.cfg_dict, k)
        rep = self._shardings(jax.eval_shape(init, key))
        params = jax.jit(init, out_shardings=self.param_sh)(key)
        state, shard = self.init_state(params)
        del params
        self.batches = traffic_mod.lm_batches(
            self.traffic, jax_key(self.seed, 1), self.cfg.vocab_size,
            sharding=self.data)
        mesh = self.mesh if (self.knobs.get("comm") == "vci"
                             or len(self.devs) > 1) else None
        lr = self.hp["lr"]
        fn = make_train_step(self.cfg, mesh=mesh, lr_fn=lambda s: lr,
                             max_grad_norm=self.hp["max_grad_norm"],
                             **self.knobs)
        with jax.set_mesh(self.mesh):
            self.step = jax.jit(fn, in_shardings=(shard, self.data),
                                out_shardings=(shard, None),
                                donate_argnums=(0,))
        first = jax.jit(self.first_grad_norms, out_shardings=rep)
        change = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype("float32") - b.astype("float32"),
            p, init(k))), out_shardings=rep)

        self.losses: List[float] = []
        for i in range(self.check_cfg["steps"]):
            state, m = self.run_step(state, i)
            self.losses.append(float(m["loss"]))
            if i == 0:
                self.grad_norms = np.asarray(first(state.opt))
        self.change_norms = np.asarray(change(state.params, key))
        self.state = state
        say(f"[train] set-up steps: losses {self.losses}")

    def run_step(self, state, i):
        import jax

        with jax.set_mesh(self.mesh):
            state, m = self.step(state, self.batches[
                i % len(self.batches)])
        jax.block_until_ready(m)
        return state, m

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, annotate) -> Dict[str, Any]:
        state = self.state
        self.state = None
        i = self.check_cfg["steps"]
        steps = failed = 0
        t0 = time.perf_counter()
        while steps == 0 or time.perf_counter() - t0 < seconds:
            with annotate(f"train step {i}"):
                state, m = self.run_step(state, i)
            if not np.isfinite(float(m["loss"])):
                failed += 1
            steps += 1
            i += 1
        elapsed = time.perf_counter() - t0
        self.state = state
        self.window_steps = steps
        say(f"[train] window: {steps} steps in {elapsed:.3f} s")
        return {"elapsed_s": elapsed, "attempted": steps, "failed": failed,
                "metrics": {"train_tokens_per_s":
                            steps * self.tokens_per_step / elapsed}}

    def layer_inputs(self) -> Dict[str, Any]:
        return {"tokens_per_step": self.tokens_per_step,
                "seq_len": self.traffic["seq_len"]}

    def free(self) -> None:
        """Drop the state and the compiled step: a loaded TPU program holds
        its scratch memory until it is unloaded."""
        import jax

        self.state = None
        self.step = None
        jax.clear_caches()

    # -- correctness -------------------------------------------------------
    def reference(self, control: bool = False, fault: str = ""):
        """The reference's losses, first clipped gradient's leaf norms and
        leaf norms of the change over ``check.steps`` steps, from the same
        weights and batches. ``fault`` plants a fault in the reference put
        in the program's place: ``half`` (half of each batch left out, the
        mean taken over the rest) or ``local`` (no exchange between chips:
        each leaf follows one chip's rows alone). A step that returns its
        state unchanged reads 1 on the change and needs no run."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        ref = family(self.cfg_dict["reference"])
        cfg = self.cfg_dict
        key = jax_key(self.seed)
        rows = self.check_cfg["rows_per_block"]
        n_dev = len(self.devs)

        def spread(x):
            """Where the reference's arrays live: each leaf split over the
            chips along its largest evenly divisible axis after the first
            (the layer axis, which the forward scans)."""
            axes = sorted(range(1, len(x.shape)) or range(len(x.shape)),
                          key=lambda a: -x.shape[a])
            for ax in axes:
                if x.shape[ax] % n_dev == 0:
                    spec = [None] * len(x.shape)
                    spec[ax] = "data"
                    return NamedSharding(self.mesh, P(*spec))
            return NamedSharding(self.mesh, P())

        init = lambda k: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), ref.init_params(cfg, k))
        shapes = jax.eval_shape(init, key)
        p_sh = jax.tree_util.tree_map(spread, shapes)
        params = jax.jit(init, out_shardings=p_sh)(key)
        blk_sh = NamedSharding(self.mesh, P(None, "data"))

        def body_of(p):
            def body(acc, blk):
                t, l = blk
                val, g = jax.value_and_grad(
                    lambda q: ref.loss_sum(q, t, l, cfg, control))(p)
                return jax.tree_util.tree_map(jnp.add, acc, (val, g)), None
            return body

        def summed(p, tok, lab):
            """(summed loss, summed gradient) over rows in blocks."""
            b, s = tok.shape
            blocks = [jax.lax.with_sharding_constraint(
                x.reshape(b // rows, rows, s), blk_sh) for x in (tok, lab)]
            zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, p))
            return jax.lax.scan(body_of(p), zero, tuple(blocks))[0]

        def grads_of(p, batch, fault):
            tok, lab = batch["tokens"], batch["labels"]
            b, s = tok.shape
            if fault == "half":
                tok, lab = tok[: b // 2], lab[: b // 2]
                b = b // 2
            val, g = summed(p, tok, lab)
            g = jax.tree_util.tree_map(lambda a: a / (b * s), g)
            if fault == "local":
                # with no exchange each chip steps on the rows it holds,
                # and each leaf is (for the most part) one chip's to update
                q = b // n_dev
                per = [summed(p, tok[r * q:(r + 1) * q],
                              lab[r * q:(r + 1) * q])[1]
                       for r in range(n_dev)]
                leaves, tdef = jax.tree_util.tree_flatten(g)
                g = jax.tree_util.tree_unflatten(tdef, [
                    jax.tree_util.tree_leaves(per[i % n_dev])[i] / (q * s)
                    for i in range(len(leaves))])
            return val / (b * s), g

        hp = self.hp

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def one_step(p, opt, batch):
            loss, g = grads_of(p, batch, fault)
            g, _ = ref_adamw.clip(g, hp["max_grad_norm"])
            norms = leaf_norms(g)
            p, opt = ref_adamw.step(p, g, opt, hp)
            return loss, norms, p, opt

        opt = jax.jit(ref_adamw.init)(params)
        losses = []
        for i in range(self.check_cfg["steps"]):
            loss, norms, params, opt = one_step(params, opt, self.batches[i])
            losses.append(float(loss))
            if i == 0:
                grad_norms = np.asarray(norms)
        del opt
        # the starting weights are made again from the seed, not kept
        change = np.asarray(jax.jit(lambda a, k: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, init(k))))(params, key))
        return losses, grad_norms, change

    def compare(self, ref_losses, ref_grads, ref_change) -> Dict[str, float]:
        """The three numbers the check compares. Leaves whose reference
        gradient is under a thousandth of the median leaf's move by
        round-off alone and are left out."""
        keep = ref_grads >= 1e-3 * np.median(ref_grads)
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(self.losses, ref_losses))
        return {"loss_gap": float(loss_gap),
                "grad_gap": gap(self.grad_norms, ref_grads, keep),
                "change_gap": gap(self.change_norms, ref_change, keep)}

    def check(self) -> List[Compared]:
        limits = self.check_cfg["limits"]
        t0 = time.perf_counter()
        got = self.compare(*self.reference())
        say(f"[train] check: reference of {self.check_cfg['steps']} steps in "
            f"{time.perf_counter() - t0:.3f} s")
        return [Compared(k, v, limits[k]) for k, v in got.items()]
