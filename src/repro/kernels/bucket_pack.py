"""Gradient-bucket pack/unpack — Pallas TPU (scalar prefetch).

The paper's per-VCI request cache keeps each stream's staging memory
private; the training-loop analogue packs a bucket's gradient shards into
one flat, tile-aligned send buffer before the bucketed all-reduce
(`repro.core.bucketing.pack_bucket` is the XLA path built from
concatenates). For many small leaves the XLA path materializes one copy
per concat operand; this kernel instead DMAs each destination tile
straight from its source segment, driven by prefetched index tables (the
same scalar-prefetch pattern as `moe_gather`).

Both directions of the fast path live here:

* :func:`bucket_pack_pallas`   — arena tiles -> one bucket's send buffer;
* :func:`bucket_unpack_pallas` — reduced bucket buffers -> arena tiles
  (the inverse DMA, same kernel body with the index tables swapped);
* :func:`bucket_pack_gather` / :func:`bucket_unpack_gather` — the exact
  vectorized-jnp lowering of the same tile-gather (one row gather + tail
  mask); reference semantics on backends without a Pallas TPU pipeline.
  (XLA:CPU scalarizes gathers, so ``reduce_gradients`` lowers the pack on
  non-TPU backends to per-slot dynamic_update_slice DMA writes instead —
  same layout contract, same bytes; see ``repro.core.bucketing``.)
* :func:`bucket_pack_ref` / :func:`bucket_unpack_ref` — scalar jnp oracles
  for the interpret-mode kernel tests.

Layout contract: segments (leaf flats) sit at TILE-ALIGNED offsets in
both the source arena and the destination buffer — the alignment the
paper's "cache-line aware VCI" optimization prescribes (§4.3) and that
``plan_buckets(align=TILE, slot_align=TILE)`` produces. A destination tile
therefore maps to exactly one source segment; tail tiles zero-fill past
``valid``. Index tables are host-side numpy (:func:`build_tile_tables`,
:func:`arena_layout`) so a persistent ``CommPlan`` can precompute them once
per (treedef, shapes) and reuse them across steps and retraces.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8 * 128


def build_tile_tables(src_off, dst_off, sizes, padded_size: int,
                      tile: int = TILE) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: per-destination-tile (source block index, valid count).

    ``src_off``/``dst_off`` must be tile-aligned (see module docstring).
    Returns (block: int32[n_tiles], valid: int32[n_tiles]).
    """
    assert padded_size % tile == 0
    src_off = np.asarray(src_off)
    dst_off = np.asarray(dst_off)
    sizes = np.asarray(sizes)
    assert (src_off % tile == 0).all(), "source segments must be tile-aligned"
    assert (dst_off % tile == 0).all(), "dest segments must be tile-aligned"
    n_tiles = padded_size // tile
    block = np.zeros((n_tiles,), np.int32)
    valid = np.zeros((n_tiles,), np.int32)
    order = np.argsort(dst_off)
    for i in order:
        n_seg_tiles = -(-int(sizes[i]) // tile)
        t0 = int(dst_off[i]) // tile
        for k in range(n_seg_tiles):
            block[t0 + k] = int(src_off[i]) // tile + k
            valid[t0 + k] = min(tile, int(sizes[i]) - k * tile)
    return block, valid


# Scalar-prefetched tables live in SMEM (1 MiB on v5e), two int32 entries
# per tile: one call covers at most this many tiles, and a longer buffer is
# written chunk by chunk into one aliased output.
MAX_TILES_PER_CALL = 1 << 16


def _kernel(block_ref, valid_ref, src_ref, out_ref, *, tile: int):
    t = pl.program_id(0)
    v = valid_ref[t]
    idx = jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    out_ref[...] = jnp.where(idx < v, src_ref[...], 0.0).astype(out_ref.dtype)


def _chunk_kernel(block_ref, valid_ref, src_ref, prev_ref, out_ref, *,
                  tile: int):
    del prev_ref  # aliased to out_ref: tiles outside this chunk keep it
    _kernel(block_ref, valid_ref, src_ref, out_ref, tile=tile)


def bucket_pack_pallas(src: jax.Array, block: jax.Array, valid: jax.Array,
                       padded_size: int, *, tile: int = TILE,
                       interpret: bool = False) -> jax.Array:
    """src: flat tile-aligned arena; returns the (padded_size,) packed
    buffer. ``block``/``valid`` from :func:`build_tile_tables`; the
    BlockSpec index_map consumes the prefetched ``block`` table so each
    grid step DMAs exactly one source tile."""
    assert padded_size % tile == 0
    assert src.shape[0] % tile == 0
    n_tiles = padded_size // tile
    block = jnp.asarray(block, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    out_shape = jax.ShapeDtypeStruct((padded_size,), src.dtype)
    in_tile = pl.BlockSpec((tile,), lambda t, b, v: (b[t],))
    out = None
    for t0 in range(0, n_tiles, MAX_TILES_PER_CALL):
        n = min(MAX_TILES_PER_CALL, n_tiles - t0)
        out_tile = pl.BlockSpec((tile,), lambda t, b, v, t0=t0: (t + t0,))
        tables = (block[t0:t0 + n], valid[t0:t0 + n])
        if out is None:
            kernel, in_specs, args, aliases = _kernel, [in_tile], (src,), {}
        else:
            kernel = _chunk_kernel
            in_specs = [in_tile, pl.BlockSpec(memory_space=pl.ANY)]
            args, aliases = (src, out), {3: 0}
        out = pl.pallas_call(
            functools.partial(kernel, tile=tile),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n,), in_specs=in_specs,
                out_specs=out_tile),
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret,
        )(*tables, *args)
    return out


def bucket_unpack_pallas(packed: jax.Array, block: jax.Array,
                         valid: jax.Array, out_size: int, *,
                         tile: int = TILE,
                         interpret: bool = False) -> jax.Array:
    """Inverse DMA: gather ``packed``'s tiles back into arena layout.

    ``packed`` is the (concatenated) reduced bucket buffer(s); ``block``
    maps each destination (arena) tile to its source tile inside
    ``packed``; ``valid`` zero-fills each tile's tail past the segment end.
    Same kernel body as the pack direction — only the host-built index
    tables differ (:func:`build_tile_tables` with src/dst roles swapped).
    """
    return bucket_pack_pallas(packed, block, valid, out_size, tile=tile,
                              interpret=interpret)


def bucket_pack_ref(src, block, valid, padded_size: int,
                    tile: int = TILE) -> jax.Array:
    """Pure-jnp oracle."""
    n_tiles = padded_size // tile
    out = jnp.zeros((padded_size,), src.dtype)
    for t in range(n_tiles):
        b = int(block[t])
        v = int(valid[t])
        seg = jax.lax.dynamic_slice(src, (b * tile,), (tile,))
        idx = jnp.arange(tile)
        seg = jnp.where(idx < v, seg, 0.0)
        out = jax.lax.dynamic_update_slice(out, seg.astype(src.dtype),
                                           (t * tile,))
    return out


def bucket_unpack_ref(packed, block, valid, out_size: int,
                      tile: int = TILE) -> jax.Array:
    """Pure-jnp oracle for the unpack direction (same gather semantics)."""
    return bucket_pack_ref(packed, block, valid, out_size, tile=tile)


def bucket_pack_gather(src: jax.Array, block, valid, padded_size: int,
                       tile: int = TILE) -> jax.Array:
    """Vectorized jnp lowering of the pack kernel for non-TPU backends:
    ONE row-gather of the source's tiles plus a tail mask — numerically
    identical to :func:`bucket_pack_pallas`, but a 2-op XLA program
    instead of a Python-stepped interpret-mode grid."""
    assert padded_size % tile == 0 and src.shape[0] % tile == 0
    block = jnp.asarray(block, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    tiles = src.reshape(-1, tile)[block]                  # (n_tiles, tile)
    lane = jnp.arange(tile, dtype=jnp.int32)[None, :]
    tiles = jnp.where(lane < valid[:, None], tiles, 0).astype(src.dtype)
    return tiles.reshape(padded_size)


def bucket_unpack_gather(packed: jax.Array, block, valid, out_size: int,
                         tile: int = TILE) -> jax.Array:
    """Vectorized jnp lowering of the unpack direction."""
    return bucket_pack_gather(packed, block, valid, out_size, tile=tile)


def arena_layout(sizes, tile: int = TILE) -> Tuple[np.ndarray, int]:
    """Host-side arena layout: each leaf (by flat ``sizes``) at the next
    tile-aligned offset. Returns (offsets: int64[n], total arena size)."""
    offs = np.zeros((len(sizes),), np.int64)
    cur = 0
    for i, sz in enumerate(sizes):
        offs[i] = cur
        cur += -(-int(sz) // tile) * tile
    return offs, max(int(cur), tile)


def arena_from_leaves(leaves, tile: int = TILE, dtype=None):
    """Lay leaves into a tile-aligned flat arena; returns (arena, offsets)."""
    offs = []
    parts = []
    cur = 0
    for leaf in leaves:
        flat = jnp.ravel(leaf)
        if dtype is not None:
            flat = flat.astype(dtype)
        offs.append(cur)
        pad = (-flat.shape[0]) % tile
        if pad:
            flat = jnp.pad(flat, (0, pad))
        parts.append(flat)
        cur += flat.shape[0]
    return jnp.concatenate(parts), np.array(offs, np.int64)
