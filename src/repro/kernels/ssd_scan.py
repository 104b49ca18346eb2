"""Mamba2 SSD intra-chunk kernel — Pallas TPU.

The blocked SSD algorithm (models/ssm.py) splits into a quadratic
*intra-chunk* part (MXU-friendly: three (c x c)/(c x n)/(c x p) matmuls per
chunk) and a cheap inter-chunk associative scan. This kernel computes the
intra-chunk part — per (batch*head, chunk) grid step it keeps the whole
working set (x, B, C tiles plus the (c x c) decay matrix) in VMEM, which is
exactly the materialization the pure-XLA path spills to HBM.

chunk=256, n<=128, p=64 => VMEM footprint ≈ (256² + 3·256·128) f32 ≈ 650 KB.

The inter-chunk recurrence stays in jnp (``ops.ssd_chunked``): it is
O(S/c · n · p) — negligible — and XLA's associative scan handles it well.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref, *, chunk: int):
    f32 = jnp.float32
    x = x_ref[0, 0].astype(f32)          # (c, p)
    dt = dt_ref[0, 0].astype(f32)        # (c, 1)
    cum = cum_ref[0, 0].astype(f32)      # (c, 1)
    B = b_ref[0, 0].astype(f32)          # (c, n)
    C = c_ref[0, 0].astype(f32)          # (c, n)

    # decay L[i,j] = exp(cum_i - cum_j), lower-triangular; masked before
    # the exp, which overflows above the diagonal
    diff = cum - cum.reshape(1, chunk)                       # (c, c)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.exp(jnp.where(jj <= ii, diff, -jnp.inf))

    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)     # (c, c)
    W = CB * L * dt.reshape(1, chunk)
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)      # (c, p)

    decay_end = jnp.exp(cum[chunk - 1, 0] - cum)             # (c, 1)
    Bw = B * (dt * decay_end)                                # (c, n)
    st = jax.lax.dot_general(Bw, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=f32)     # (n, p)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = st.astype(st_ref.dtype)


def ssd_chunk_pallas(x, dt, cum, B, C, *, interpret: bool = False):
    """Intra-chunk SSD over all (batch*head, chunk) pairs.

    x:   (bh, nc, c, p)
    dt:  (bh, nc, c)      positive step sizes
    cum: (bh, nc, c)      cumulative dA within the chunk
    B,C: (bh, nc, c, n)
    returns (y_intra: (bh, nc, c, p) f32, state: (bh, nc, n, p) f32)
    """
    bh, nc, c, p = x.shape
    n = B.shape[-1]
    dt2 = dt[..., None]
    cum2 = cum[..., None]
    kernel = functools.partial(_kernel, chunk=c)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, c, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, c, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, c, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, c, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, dt2, cum2, B, C)
