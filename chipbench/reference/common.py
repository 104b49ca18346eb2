"""Pieces the family references share: the float32 matrix product (and its
float8 control), norms, causal masks and the loss."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _fp8(a, axis):
    """Round ``a`` through float8 e4m3 with an absmax scale over ``axis``
    (None: the whole tensor), back in float32. The gradient passes through
    unchanged (straight-through), so a control step still trains."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return a + jax.lax.stop_gradient(q - a)


def mm(x, w, control: bool):
    """``x @ w`` in float32 at full precision. The control rounds each
    activation row and the whole weight through float8 first."""
    x = x.astype(F32)
    w = w.astype(F32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def einsum(spec, a, b, control: bool):
    a = a.astype(F32)
    b = b.astype(F32)
    if control:
        a, b = _fp8(a, None), _fp8(b, None)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def token_ce(logits, labels):
    """Per-token cross entropy (float32)."""
    logp = jax.nn.log_softmax(logits.astype(F32), -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)
