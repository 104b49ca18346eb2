"""Shared neural-net building blocks (pure JAX, functional)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def rms_norm(x, scale=None, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    return x.astype(dt)


def layer_norm(x, scale=None, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


def apply_norm(cfg: ModelConfig, x, params: Optional[dict]):
    """Dispatch on cfg.norm. ``nonparametric`` (OLMo) takes no params."""
    eps = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    if cfg.norm == "nonparametric":
        return layer_norm(x, None, None, **eps)
    if cfg.norm == "layernorm":
        return layer_norm(x, params["scale"], params.get("bias"), **eps)
    return rms_norm(x, params["scale"], **eps)


# ---------------------------------------------------------------------------
# activations / gated FFN
# ---------------------------------------------------------------------------

def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def gated_ffn(cfg: ModelConfig, x, p, shard=None, comm=None,
              purpose: str = "tp_mlp"):
    """GeGLU/SwiGLU: act(x @ w_gate) * (x @ w_up) @ w_down.

    Under the manual-TP serve path (``comm`` set) w_gate/w_up arrive
    column-sharded and w_down row-sharded, so ``h @ w_down`` is a partial
    sum: it is all-reduced on the purpose's VCI stream, and the replicated
    ``b_down`` is added AFTER the reduce (adding it to the partial would
    count it tp times).
    """
    a = act_fn(cfg.hidden_act)
    h = a(x @ p["w_gate"]) * (x @ p["w_up"])
    if "b_up" in p:
        h = h + p["b_up"]
    if shard is not None:
        h = shard.ffn_hidden(h)
    y = h @ p["w_down"]
    if comm is not None:
        y = comm.psum(y, purpose)
    if "b_down" in p:
        y = y + p["b_down"]
    return y


# ---------------------------------------------------------------------------
# gradient dtype boundary (OPT bf16_grads — EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def bf16_grad_boundary(x):
    """Identity fwd; bwd rounds the cotangent through bf16 AND returns it in
    bf16. Placed after the TP matmuls so the backward partial-sum
    all-reduces carry 2-byte payloads (the f32 norm math upstream otherwise
    makes XLA hoist a convert-to-f32 BEFORE the all-reduce, doubling link
    bytes)."""
    return x


def _bf16_fwd(x):
    return x, None


def _bf16_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


bf16_grad_boundary.defvjp(_bf16_fwd, _bf16_bwd)


def maybe_bf16_grads(cfg: ModelConfig, x):
    if "bf16_grads" in cfg.opts:
        return bf16_grad_boundary(x)
    return x


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if ang.ndim == 2:  # (S, hd/2) -> broadcast batch
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]    # (B,S,1,hd/2)
    x1, x2 = x[..., ::2], x[..., 1::2]
    dt = x.dtype
    x1, x2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dt)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = -2, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * 0.02).astype(dtype)
