"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel sweeps in ``tests/test_kernels.py``
assert against (``interpret=True`` execution of the kernels on CPU).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash attention oracle
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None) -> jax.Array:
    """q: (B,H,Sq,hd); k/v: (B,KV,Skv,hd). GQA via head broadcast."""
    b, h, sq, hd = q.shape
    kv = k.shape[1]
    rep = h // kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# SSD intra-chunk oracle
# ---------------------------------------------------------------------------

def ssd_chunk_ref(x, dt, cum, B, C) -> Tuple[jax.Array, jax.Array]:
    """One chunk, one head.

    x: (c, p); dt: (c,); cum: (c,) cumulative dA; B, C: (c, n)
    returns (y_intra: (c, p), state: (n, p))
    """
    c = x.shape[0]
    f32 = jnp.float32
    x, dt, cum, B, C = (t.astype(f32) for t in (x, dt, cum, B, C))
    L = jnp.exp(jnp.where(jnp.tril(jnp.ones((c, c), bool)),
                          cum[:, None] - cum[None, :], -jnp.inf))
    W = (C @ B.T) * L * dt[None, :]
    y = W @ x
    decay_end = jnp.exp(cum[-1] - cum)
    state = (B * (dt * decay_end)[:, None]).T @ x          # (n, p)
    return y, state


def ssd_chunk_batched_ref(x, dt, cum, B, C):
    """x: (bh, nc, c, p); dt/cum: (bh, nc, c); B/C: (bh, nc, c, n)."""
    f = jax.vmap(jax.vmap(ssd_chunk_ref))
    return f(x, dt, cum, B, C)


# ---------------------------------------------------------------------------
# bucket pack oracle
# ---------------------------------------------------------------------------

def pack_ref(src: jax.Array, src_off: np.ndarray, dst_off: np.ndarray,
             sizes: np.ndarray, dst_size: int) -> jax.Array:
    """Copy ``len(sizes)`` segments from a flat source arena into an aligned
    destination buffer (zeros elsewhere)."""
    dst = jnp.zeros((dst_size,), src.dtype)
    for so, do, n in zip(src_off, dst_off, sizes):
        dst = jax.lax.dynamic_update_slice(
            dst, jax.lax.dynamic_slice(src, (int(so),), (int(n),)), (int(do),))
    return dst
