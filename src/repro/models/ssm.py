"""Mamba2 / SSD (state-space duality) layer [arXiv:2405.21060].

Train/prefill uses the blocked SSD algorithm: the sequence is split into
chunks of ``chunk_size``; within a chunk the quadratic (attention-dual) form
runs on the MXU, across chunks a low-rank state recurrence propagates the
``(H, N, P)`` state via an associative scan. Decode is the O(1) recurrent
update.

This module is the pure-jnp reference implementation used by the model's XLA
path; ``repro.kernels.ssd_scan`` is the Pallas TPU kernel for the intra-chunk
part, validated against :func:`ssd_chunked` in tests.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rms_norm


# ---------------------------------------------------------------------------
# the SSD scan itself (head-parallel; f32 internally)
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, *, chunk: int,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Blocked SSD.

    x:  (b, s, h, p)   values
    dt: (b, s, h)      positive step sizes (already softplus'd + bias)
    A:  (h,)           negative per-head decay rates
    B:  (b, s, g, n)   input projections  (g groups broadcast over heads)
    C:  (b, s, g, n)   output projections
    returns (y: (b,s,h,p), final_state: (b,h,n,p))
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    s_orig = s
    if s % chunk:
        # pad with dt=0 steps: decay exp(0)=1 keeps the state, dt_j=0 zeroes
        # the padded tokens' contributions — exact for y[:s] and final_state.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nc = s // chunk
    rep = h // g

    f32 = jnp.float32
    xs = x.reshape(b, nc, chunk, h, p).astype(f32)
    dts = dt.reshape(b, nc, chunk, h).astype(f32)
    Bs = B.reshape(b, nc, chunk, g, n).astype(f32)
    Cs = C.reshape(b, nc, chunk, g, n).astype(f32)

    dA = dts * A.astype(f32)                                 # (b,nc,c,h)
    cum = jnp.cumsum(dA, axis=2)                             # (b,nc,c,h)
    cum_end = cum[:, :, -1:, :]                              # (b,nc,1,h)

    # ---- intra-chunk (quadratic/dual form) --------------------------------
    # L[i,j] = exp(cum_i - cum_j) for j <= i, else 0. Masked before the exp:
    # above the diagonal cum_i - cum_j > 0 grows with the chunk, and its exp
    # overflows to inf, whose masked-out gradient is NaN.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (b,nc,i,j,h)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    Li = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    CB = jnp.einsum("bnigq,bnjgq->bnijg", Cs, Bs)            # (b,nc,i,j,g)
    CB = jnp.repeat(CB, rep, axis=4)                         # -> heads
    W = CB * Li * dts[:, :, None, :, :]                      # weight on x_j
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", W, xs)

    # ---- per-chunk local states --------------------------------------------
    decay_end = jnp.exp(cum_end - cum)                       # (b,nc,c,h)
    Br = jnp.repeat(Bs, rep, axis=3)                         # groups -> heads
    Bx = jnp.einsum("bnchq,bnchp,bnch->bnhqp",
                    Br, xs, dts * decay_end)                 # (b,nc,h,n,p)

    # ---- inter-chunk recurrence (associative scan) -------------------------
    a = jnp.exp(cum_end[:, :, 0, :])                         # (b,nc,h)
    a_full = a[..., None, None]                              # (b,nc,h,1,1)

    def op(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, a2 * s1 + s2

    if initial_state is not None:
        init = initial_state.astype(f32)[:, None]            # (b,1,h,n,p)
        ones = jnp.ones((b, 1, h, 1, 1), f32)
        a_full = jnp.concatenate([ones, a_full], axis=1)
        Bx = jnp.concatenate([init, Bx], axis=1)
    acc_a, acc_s = jax.lax.associative_scan(op, (a_full, Bx), axis=1)
    if initial_state is not None:
        acc_s_incl = acc_s[:, 1:]
    else:
        acc_s_incl = acc_s
    final_state = acc_s_incl[:, -1]                          # (b,h,n,p)
    # state ENTERING chunk k = inclusive state after chunk k-1
    zeros = jnp.zeros((b, 1, h, n, p), f32)
    if initial_state is not None:
        s_prev = jnp.concatenate([init, acc_s_incl[:, :-1]], axis=1)
    else:
        s_prev = jnp.concatenate([zeros, acc_s_incl[:, :-1]], axis=1)

    decay_in = jnp.exp(cum)                                  # (b,nc,c,h)
    Cr = jnp.repeat(Cs, rep, axis=3)                         # (b,nc,c,h,n)
    y_inter = jnp.einsum("bnchq,bnhqp,bnch->bnchp", Cr, s_prev, decay_in)

    y = (y_intra + y_inter).reshape(b, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), final_state


def ssd_decode_step(state, x, dt, A, B, C):
    """O(1) recurrent step.

    state: (b,h,n,p); x: (b,h,p); dt: (b,h); A: (h,); B,C: (b,g,n)
    returns (y: (b,h,p), new_state)
    """
    f32 = jnp.float32
    rep = x.shape[1] // B.shape[1]
    Bh = jnp.repeat(B.astype(f32), rep, axis=1)              # (b,h,n)
    Ch = jnp.repeat(C.astype(f32), rep, axis=1)
    dtf = dt.astype(f32)
    decay = jnp.exp(dtf * A.astype(f32))[..., None, None]    # (b,h,1,1)
    inject = jnp.einsum("bhq,bhp,bh->bhqp", Bh, x.astype(f32), dtf)
    new_state = decay * state.astype(f32) + inject
    y = jnp.einsum("bhq,bhqp->bhp", Ch, new_state)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# the full Mamba2 block (projections + conv + scan + gated norm)
# ---------------------------------------------------------------------------

class SSMState(NamedTuple):
    conv: jax.Array   # (b, conv_width-1, d_conv_channels)
    ssd: jax.Array    # (b, h, n, p)

    @classmethod
    def init(cls, cfg: ModelConfig, batch: int, dtype=jnp.float32) -> "SSMState":
        c = cfg.ssm
        d_in = c.d_inner(cfg.d_model)
        ch = d_in + 2 * c.ngroups * c.d_state
        h = c.num_heads(cfg.d_model)
        return cls(
            jnp.zeros((batch, c.conv_width - 1, ch), dtype),
            jnp.zeros((batch, h, c.d_state, c.head_dim), jnp.float32),
        )


def _split_proj(cfg: ModelConfig, zxbcdt):
    c = cfg.ssm
    d_in = c.d_inner(cfg.d_model)
    d_bc = 2 * c.ngroups * c.d_state
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + d_bc], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc, w, bias=None):
    """Depthwise causal conv. xbc: (b,s,ch); w: (width, ch); bias: (ch,)."""
    width = w.shape[0]
    pad = jnp.zeros_like(xbc[:, : width - 1])
    xp = jnp.concatenate([pad, xbc], axis=1)
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    for i in range(width):
        out = out + xp[:, i: i + xbc.shape[1]].astype(jnp.float32) * w[i].astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(xbc.dtype)


def _gate_norm(cfg: ModelConfig, y, z, scale):
    """The gated RMSNorm ``norm(y * silu(z))``."""
    eps = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    return rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                    scale, **eps)


def mamba2_forward(cfg: ModelConfig, x, p, shard=None,
                   start: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, SSMState]:
    """Full-sequence Mamba2 block from a zero state. x: (b,s,d) -> (y:
    (b,s,d), final state).

    ``start`` — (B,) int32 left-pad lengths: positions below ``start[b]``
    take no step (``dt = 0``, so the state is left as it was) and feed the
    conv a zero input (what an unpadded row's causal conv sees there), so a
    padded row's outputs and final state are an unpadded row's."""
    c = cfg.ssm
    b, s, _ = x.shape
    d_in = c.d_inner(cfg.d_model)
    h = c.num_heads(cfg.d_model)

    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    if start is not None:
        real = (jnp.arange(s)[None, :] >= start[:, None])[..., None]
        xbc = jnp.where(real, xbc, 0)
    conv_in = xbc
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p.get("conv_b")))
    xv, B, C = jnp.split(xbc, [d_in, d_in + c.ngroups * c.d_state], axis=-1)
    xv = xv.reshape(b, s, h, c.head_dim)
    B = B.reshape(b, s, c.ngroups, c.d_state)
    C = C.reshape(b, s, c.ngroups, c.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if start is not None:
        dt = jnp.where(real, dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if shard is not None:
        xv = shard.heads(xv)

    y, final = ssd_chunked(xv, dt, A, B, C, chunk=c.chunk_size)
    y = y + xv * p["D"].astype(jnp.float32)[None, None, :, None].astype(xv.dtype)
    y = _gate_norm(cfg, y.reshape(b, s, d_in), z, p["gate_norm"])
    out = y @ p["out_proj"].astype(y.dtype)

    # conv tail state for decode continuation: the last raw conv inputs
    pad_needed = c.conv_width - 1
    conv_state = conv_in[:, -pad_needed:] if s >= pad_needed else jnp.pad(
        conv_in, ((0, 0), (pad_needed - s, 0), (0, 0)))
    return out, SSMState(conv_state, final)


def mamba2_decode(cfg: ModelConfig, x, p, state: SSMState,
                  shard=None) -> Tuple[jax.Array, SSMState]:
    """One-token Mamba2 step. x: (b,1,d)."""
    c = cfg.ssm
    b = x.shape[0]
    d_in = c.d_inner(cfg.d_model)
    h = c.num_heads(cfg.d_model)

    zxbcdt = x[:, 0] @ p["in_proj"].astype(x.dtype)          # (b, proj)
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    # conv over [state ; new]
    window = jnp.concatenate([state.conv, xbc[:, None]], axis=1)  # (b,w,ch)
    w = p["conv_w"].astype(jnp.float32)
    xbc = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w)
    if "conv_b" in p:
        xbc = xbc + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc).astype(x.dtype)
    new_conv = window[:, 1:].astype(state.conv.dtype)

    xv, B, C = jnp.split(xbc, [d_in, d_in + c.ngroups * c.d_state], axis=-1)
    xv = xv.reshape(b, h, c.head_dim)
    B = B.reshape(b, c.ngroups, c.d_state)
    C = C.reshape(b, c.ngroups, c.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    y, new_ssd = ssd_decode_step(state.ssd, xv, dt, A, B, C)
    y = y + xv * p["D"].astype(jnp.float32)[None, :, None].astype(xv.dtype)
    y = _gate_norm(cfg, y.reshape(b, d_in), z, p["gate_norm"])
    out = (y @ p["out_proj"].astype(y.dtype))[:, None]
    return out, SSMState(new_conv, new_ssd)
