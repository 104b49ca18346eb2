"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060):
pre-norm (RMSNorm) residual blocks of in-projection to ``[z, x, B, C, dt]``,
a depthwise causal convolution and SiLU over ``[x, B, C]``, the selective
state-space map with scalar per-head decay, a skip ``D x``, the gated
RMSNorm ``norm(y * silu(z))`` and an out-projection; a final RMSNorm and a
head tied to the embedding.

The state-space map is written in its quadratic ("attention") form over the
whole sequence, the definition the paper's chunked algorithm computes:
``y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r = s+1}^{t} dt_r A) dt_s x_s``.
The decay is exponentiated only where ``s <= t``, so no term overflows.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (F32, einsum, mm, normal, rms_norm,
                                        token_ce)


def _dims(cfg):
    s = cfg["ssm"]
    d = cfg["d_model"]
    d_in = s["expand"] * d
    nh = d_in // s["head_dim"]
    d_bc = 2 * s["ngroups"] * s["d_state"]
    return d, d_in, nh, d_bc, s


def init_params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """The benchmark's weights in the program's layout. A and dt follow the
    paper's initialisation: A in [1, 16], dt log-uniform in [1e-3, 1e-1]."""
    L, v = cfg["num_layers"], cfg["vocab_size"]
    d, d_in, nh, d_bc, s = _dims(cfg)
    dt_ = jnp.dtype(cfg["param_dtype"])
    ks = jax.random.split(key, 6)
    dt0 = jnp.exp(jax.random.uniform(ks[4], (L, nh), F32)
                  * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "embed": {"tok": normal(ks[0], (v, d), 0.02, dt_)},
        "layers": {
            "ssm": {
                "in_proj": normal(ks[1], (L, d, 2 * d_in + d_bc + nh),
                                  d ** -0.5, dt_),
                "conv_w": normal(ks[2], (L, s["conv_width"], d_in + d_bc),
                                 0.1, dt_),
                "A_log": jnp.log(jax.random.uniform(ks[5], (L, nh), F32,
                                                    1.0, 16.0)),
                "D": jnp.ones((L, nh), F32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "gate_norm": jnp.ones((L, d_in), F32),
                "out_proj": normal(ks[3], (L, d_in, d), d_in ** -0.5, dt_),
            },
            "norm1": {"scale": jnp.ones((L, d), F32)},
        },
        "final_norm": {"scale": jnp.ones((d,), F32)},
    }


def _ssd(x, dt, A, B, C, control):
    """One sequence. x: (S, H, P); dt: (S, H); A: (H,); B, C: (S, G, N)."""
    s, h, _ = x.shape
    g = B.shape[1]
    cum = jnp.cumsum(dt * A, axis=0)                          # (S, H)
    lower = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    diff = cum[:, None, :] - cum[None, :, :]                  # (t, s, H)
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    cb = einsum("tgn,sgn->gts", C, B, control)                # (G, t, s)
    cb = jnp.repeat(cb, h // g, axis=0)                       # (H, t, s)
    w = jnp.transpose(cb, (1, 2, 0)) * decay * dt[None, :, :]  # (t, s, H)
    return einsum("tsh,shp->thp", w, x, control)


def _conv(xbc, w):
    """Depthwise causal convolution of one sequence. xbc: (S, ch)."""
    k = w.shape[0]
    xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    return sum(xp[i: i + xbc.shape[0]] * w[i] for i in range(k))


def _block(cfg, x, p, control):
    """One sequence x: (S, d)."""
    d, d_in, nh, d_bc, s = _dims(cfg)
    eps = cfg.get("norm_eps", 1e-5)
    q = p["ssm"]
    y = rms_norm(x, eps) * p["norm1"]["scale"]
    zxbcdt = mm(y, q["in_proj"], control)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + d_bc], -1)
    xbc = jax.nn.silu(_conv(xbc, q["conv_w"].astype(F32)))
    xs, B, C = jnp.split(xbc, [d_in, d_in + d_bc // 2], -1)
    n = s["d_state"]
    xs = xs.reshape(-1, nh, s["head_dim"])
    B = B.reshape(-1, s["ngroups"], n)
    C = C.reshape(-1, s["ngroups"], n)
    dt = jax.nn.softplus(dt + q["dt_bias"])
    A = -jnp.exp(q["A_log"])
    out = _ssd(xs, dt, A, B, C, control) + xs * q["D"][None, :, None]
    out = out.reshape(-1, d_in) * jax.nn.silu(z)
    out = rms_norm(out, eps) * q["gate_norm"]
    return x + mm(out, q["out_proj"], control)


def hidden(params, tokens, cfg, control: bool = False):
    """Final hidden states (B, S, d), float32, one sequence at a time."""
    eps = cfg.get("norm_eps", 1e-5)

    @jax.checkpoint
    def body(x, p):
        return jax.vmap(lambda r: _block(cfg, r, p, control))(x), None

    x = params["embed"]["tok"].astype(F32)[tokens]
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, eps) * params["final_norm"]["scale"]


def unembed(params, x, cfg, control: bool = False):
    return mm(x, params["embed"]["tok"].T, control)


def forward(params, tokens, cfg, control: bool = False):
    with jax.default_matmul_precision("highest"):
        return unembed(params, hidden(params, tokens, cfg, control), cfg,
                       control)


def loss_sum(params, tokens, labels, cfg, control: bool = False):
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, cfg, control)
        return jnp.sum(token_ce(unembed(params, x, cfg, control), labels))
