"""A plain AdamW step in float32: global-norm clipping, bias-corrected
moments, and decoupled weight decay on leaves of two or more dimensions.
The hyperparameters come from the cell file."""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp


class State(NamedTuple):
    m: Any
    v: Any
    count: jax.Array


def init(params) -> State:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return State(zeros, zeros, jnp.zeros((), jnp.int32))


def clip(grads, max_norm):
    """(clipped grads, global norm before clipping)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    if max_norm is None:
        return grads, norm
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


def step(params, grads, state: State, hp: Dict[str, float]):
    """One update from already-clipped ``grads``; returns (params, state)."""
    b1, b2 = hp["b1"], hp["b2"]
    count = state.count + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state.m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state.v, grads)

    def upd(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
        if p.ndim >= 2:
            u = u + hp["weight_decay"] * p
        return p - hp["lr"] * u

    params = jax.tree_util.tree_map(upd, params, m, v)
    return params, State(m, v, count)
