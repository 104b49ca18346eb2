"""The plain float32 references against the program at its ``-smoke`` sizes
(float32 weights and activations, so the two agree to rounding), and the
float8 control that the check must tell apart from the program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import adamw as ref_adamw
from chipbench.reference import family


def _setup(arch):
    from repro.configs import get_config
    from repro.models.transformer import init_params

    cfg = get_config(arch)
    d = dataclasses.asdict(cfg)
    d["norm_eps"] = 1e-5 if cfg.norm != "rmsnorm" else 1e-6
    ref = family("ssm" if cfg.family == "ssm" else "dense")
    params = jax.jit(lambda k: ref.init_params(d, k))(jax.random.key(7))
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    toks = jax.random.randint(jax.random.key(8), (2, 48), 0, cfg.vocab_size)
    return cfg, d, ref, params, toks


@pytest.mark.parametrize("arch", ["olmo-1b-smoke", "mamba2-780m-smoke"])
def test_forward_matches_program(arch):
    from repro.models.transformer import Model

    cfg, d, ref, params, toks = _setup(arch)
    with jax.default_matmul_precision("highest"):
        prog = Model(cfg).forward(params, {"tokens": toks})[0]
    want = ref.forward(params, toks, d)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(prog - want).max()) <= 1e-5 * scale
    # the float8 control lies far outside that agreement
    ctrl = ref.forward(params, toks, d, control=True)
    assert float(jnp.abs(ctrl - want).max()) >= 1e-2 * scale


def test_dense_decode_positions_match():
    """Prefill of a prompt, then decode through the program's paged cache:
    every position's logits agree with one causal reference forward."""
    from repro.models.transformer import Model, init_paged_cache

    cfg, d, ref, params, toks = _setup("olmo-1b-smoke")
    model = Model(cfg)
    plen, total = 20, 32
    cache = init_paged_cache(cfg, 1, 64, page_size=16, num_pages=5,
                             dtype=jnp.float32)
    table = jnp.full_like(cache.kv.table, -1).at[0, :4].set(
        jnp.arange(1, 5))
    kv = cache.kv
    cache = cache._replace(kv=type(kv)(kv.k, kv.v, table, kv.length,
                                       kv.page_size))
    with jax.default_matmul_precision("highest"):
        logits, _, cache = model.forward(params, {"tokens": toks[:1, :plen]},
                                         cache=cache)
        got = [logits[0]]
        for t in range(plen, total):
            lg, cache = model.decode_step(params, toks[:1, t:t + 1], cache)
            got.append(lg[0])
    got = jnp.concatenate(got)
    want = ref.forward(params, toks[:1, :total], d)[0]
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def test_ssm_loss_and_grads_match_program():
    from repro.models.transformer import Model
    from repro.train.losses import total_loss

    cfg, d, ref, params, toks = _setup("mamba2-780m-smoke")
    labels = jnp.roll(toks, -1, 1)

    def prog_loss(p):
        lg, aux, _ = Model(cfg).forward(p, {"tokens": toks})
        return total_loss(cfg, lg, labels, aux)[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss_sum(p, toks, labels, d) / toks.size)(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(
            jnp.abs(b).max()) + 1e-12


def test_adamw_step_matches_program():
    from repro.optim.adamw import adamw_init, adamw_update

    k1, k2 = jax.random.split(jax.random.key(3))
    params = {"w": jax.random.normal(k1, (8, 4)), "b": jnp.ones((4,))}
    grads = {"w": 3.0 * jax.random.normal(k2, (8, 4)),
             "b": jnp.full((4,), 0.5)}
    hp = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
          "weight_decay": 0.1, "max_grad_norm": 1.0}
    p_prog, s_prog = params, adamw_init(params)
    p_ref, s_ref = params, ref_adamw.init(params)
    for _ in range(3):
        p_prog, s_prog, _ = adamw_update(grads, s_prog, p_prog,
                                         lr=jnp.float32(hp["lr"]))
        g, _ = ref_adamw.clip(grads, hp["max_grad_norm"])
        p_ref, s_ref = ref_adamw.step(p_ref, g, s_ref, hp)
    for a, b in zip(jax.tree_util.tree_leaves(p_prog),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
