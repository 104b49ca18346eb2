"""Blockwise flash attention — Pallas TPU kernel.

TPU-native adaptation: (Bq, hd) query tiles live in VMEM; the kernel walks
KV blocks along the innermost ("arbitrary") grid dimension, keeping the
online-softmax running max/denominator and the output accumulator in VMEM
scratch across iterations. MXU-aligned block shapes (multiples of 128 on the
matmul dims) are chosen by ``repro.kernels.ops.flash_attention``.

Supports causal masking, sliding windows (SWA) and GQA (the KV index map
folds the query head onto its KV group), with block-level early-out for
fully-masked tiles.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, seq_k: int,
            causal: bool, window: Optional[int]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level mask decision (static per grid step at trace time is not
    # possible — q_start/k_start are dynamic — so use pl.when on scalars)
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window is not None:
        run &= k_start + block_k - 1 > q_start - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                # (Bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                # (Bk, hd)
        # zero padded KV rows: padding memory is unspecified, and 0 * NaN
        # would poison the accumulator even under a fully-masked p.
        kv_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], 1), 0)) < seq_k
        v = jnp.where(kv_valid, v, 0.0)
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kp < seq_k
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # (Bq,1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, Sq, hd); k/v: (B, KV, Skv, hd) -> (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    rep = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki, rep=rep: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki, rep=rep: (b, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
