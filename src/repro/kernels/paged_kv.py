"""Paged KV-cache page gather — Pallas TPU (scalar prefetch).

The paged serve cache stores K/V in a fixed pool of fixed-size pages
(``(L, num_pages, page_size, KV, hd)``, stacked over layers) with a per-slot
page table shared by every layer; attention needs one layer's pages of each
slot laid out contiguously in sequence order.
This is the same shape of problem as the gradient-bucket pack
(`repro.kernels.bucket_pack`): a table-driven tile gather whose index
tables are known outside the kernel. The TPU kernel DMAs one pool page per
grid step straight to its destination row, driven by the prefetched layer
index and page table — unmapped entries (``-1``, pad prefix / freed slots)
emit zeros.

Three equivalent implementations, mirroring the bucket-pack layering:

* :func:`paged_gather_pallas` — the TPU scalar-prefetch kernel
  (interpret-mode tested on CPU);
* :func:`paged_gather_take`   — the vectorized one-gather lowering used
  on backends without a Pallas TPU pipeline (XLA:CPU scalarizes nothing
  here);
* :func:`paged_gather_ref`    — scalar oracle for the kernel tests.

:func:`paged_gather` dispatches on the backend; the model code calls only
this entry point.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, table_ref, pool_ref, out_ref):
    t = pl.program_id(0)
    mapped = table_ref[t] >= 0
    page = pool_ref[...]                              # (PS, KV, hd)
    page = jnp.where(mapped, page, jnp.zeros_like(page))
    out_ref[...] = page.reshape(out_ref.shape)


def paged_gather_pallas(pool: jax.Array, table: jax.Array, layer, *,
                        interpret: bool = False) -> jax.Array:
    """pool: (L, NP, PS, KV, hd) the layer-stacked page pool (or (L, NP,
    PS, KV*hd), the layout of heads narrower than a lane tile); table:
    (B, MAXP) int32 pool page ids (-1 unmapped); layer: () int32 which
    layer's pages to read. Returns (B, MAXP*PS, KV, hd) — slot b's pages
    of that layer in logical order, unmapped pages zero-filled.

    Grid = one destination page per step; the BlockSpec index_map consumes
    the prefetched layer index and (flattened) table so each step DMAs
    exactly one page of the stacked pool (clamped to page 0 for unmapped
    entries, zeroed in the kernel body). The pool is read where it lies:
    no slice of the layer and no reshape of the pool; the kernel body lays
    each ``(PS, KV, hd)`` page out as the view's ``(PS, KV*hd)`` row.
    """
    b, maxp = table.shape
    ps = pool.shape[2]
    tail = pool.shape[3:]
    e = math.prod(tail)
    flat_table = table.reshape(-1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * maxp,),
        in_specs=[
            pl.BlockSpec((None, None, ps) + tail,
                         lambda t, layer_ref, table_ref: (
                             layer_ref[0], jnp.maximum(table_ref[t], 0),
                             0) + (0,) * len(tail)),
        ],
        out_specs=pl.BlockSpec((None, ps, e),
                               lambda t, layer_ref, table_ref: (t, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * maxp, ps, e), pool.dtype),
        interpret=interpret,
    )(layer, flat_table, pool)
    return out.reshape((b, maxp * ps) + tail)


def paged_gather_take(pool: jax.Array, table: jax.Array, layer
                      ) -> jax.Array:
    """Vectorized lowering: ONE row gather of the layer's pages from the
    stacked pool plus an unmapped-page mask — numerically identical to the
    kernel."""
    b, maxp = table.shape
    ps = pool.shape[2]
    ids = jnp.clip(table, 0, pool.shape[1] - 1)
    pages = pool[layer, ids]                          # (B, MAXP, PS, KV, hd)
    mapped = (table >= 0).reshape((b, maxp) + (1,) * (pages.ndim - 2))
    pages = jnp.where(mapped, pages, jnp.zeros((), pool.dtype))
    return pages.reshape((b, maxp * ps) + pool.shape[3:])


def paged_gather_ref(pool, table) -> jax.Array:
    """Scalar jnp oracle for the interpret-mode kernel tests; ``pool`` is
    one layer's ``(NP, PS, KV, hd)`` pool."""
    b, maxp = table.shape
    ps = pool.shape[1]
    rows = []
    for i in range(b):
        pages = []
        for p in range(maxp):
            pid = int(table[i, p])
            pages.append(pool[pid] if pid >= 0
                         else jnp.zeros_like(pool[0]))
        rows.append(jnp.concatenate(pages, axis=0))
    return jnp.stack(rows).reshape((b, maxp * ps) + pool.shape[2:])


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_gather(pool: jax.Array, table: jax.Array, layer) -> jax.Array:
    """Backend dispatch: Pallas tile-gather on TPU, the one-gather
    lowering elsewhere (the CPU smoke/conformance path)."""
    if _on_tpu():
        return paged_gather_pallas(pool, table, layer)
    return paged_gather_take(pool, table, layer)
