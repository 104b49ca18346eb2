"""Table-driven row gather — Pallas TPU (scalar prefetch).

The MoE dispatch/combine hot path: move token rows into expert-capacity
buffers (and back) according to a routing table computed on the host side of
the matmuls. On GPU this is a hand-rolled scatter kernel; the TPU-native
version uses Pallas *scalar prefetch* — the routing table is prefetched to
SMEM and consumed by the BlockSpec ``index_map``, so each grid step DMAs the
right source row tile directly (the pattern paged-attention kernels use).

``idx[i] < 0`` marks an invalid row (capacity padding): the output tile is
zero-filled.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, src_ref, out_ref):
    i = pl.program_id(0)
    valid = idx_ref[i] >= 0
    out_ref[...] = jnp.where(valid, src_ref[...], 0.0).astype(out_ref.dtype)


def row_gather_pallas(src, idx, *, block_d: int = 512,
                      interpret: bool = False) -> jax.Array:
    """out[i, :] = src[idx[i], :] (0 where idx[i] < 0).

    src: (T, d); idx: (M,) int32 -> out: (M, d)

    Rows travel as ``(T, 1, d)`` with ``(1, 1, block_d)`` blocks: a block's
    last two dims must be multiples of the TPU tile ``(8, 128)`` or span
    the array, and the unit middle axis spans its array where a ``(1,
    block_d)`` block on ``(T, d)`` would not.
    """
    t, d = src.shape
    m = idx.shape[0]
    block_d = min(block_d, d)
    nd = pl.cdiv(d, block_d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, nd),
        in_specs=[
            pl.BlockSpec((1, 1, block_d),
                         lambda i, j, idx_ref: (jnp.maximum(idx_ref[i], 0),
                                                0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d),
                               lambda i, j, idx_ref: (i, 0, j)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, d), src.dtype),
        interpret=interpret,
    )(idx, src.reshape(t, 1, d))
    return out.reshape(m, d)


def row_gather_ref(src, idx) -> jax.Array:
    safe = jnp.maximum(idx, 0)
    out = src[safe]
    return jnp.where((idx >= 0)[:, None], out, 0.0).astype(src.dtype)
