"""Attention: GQA/MQA, causal + sliding-window masks, KV-cache decode.

Attention is computed by the XLA code below, but for the paged decode step
on TPU, which attends with the Pallas kernel of
:mod:`repro.kernels.paged_kv`. The Pallas flash-attention kernel
(``repro.kernels.ops.flash_attention``, tested against
``repro.kernels.ref``) is not called by the model.

Decode supports two cache layouts:

* full cache ``(B, S_max, KV, hd)`` with a write cursor;
* ring cache ``(B, W, KV, hd)`` for sliding-window archs — O(W) memory at
  any context length, which is what qualifies dense archs for ``long_500k``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

NEG_INF = -1e30


def _repeat_kv(k, n_rep: int):
    """(B,S,KV,hd) -> (B,S,KV*n_rep,hd) for GQA."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)
                            ).reshape(b, s, kv * n_rep, hd)


def softmax_scale(cfg: ModelConfig, head_dim: int) -> float:
    """The scale of attention scores: ``attention_multiplier`` where the
    config gives one (granite's muP), else ``head_dim ** -0.5``."""
    m = cfg.attention_multiplier
    return 1.0 / math.sqrt(head_dim) if m is None else m


def causal_mask(q_len: int, kv_len: int, *, window: Optional[int] = None,
                q_offset: int = 0) -> jax.Array:
    """(q_len, kv_len) bool mask. ``window`` adds the sliding-window band."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    m = k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    return m


def attention(cfg: ModelConfig, q, k, v, *, q_offset: int = 0,
              mask: Optional[jax.Array] = None,
              start: Optional[jax.Array] = None) -> jax.Array:
    """Full (prefill/train) attention. q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd).

    ``start`` — (B,) int32 left-pad lengths — masks each row's pad prefix
    (key positions ``< start[b]``) so mixed-length prompts prefill exactly
    as they would alone. ``mask`` may be (Sq, Skv) shared or (B, Sq, Skv)
    per-row.
    """
    b, sq, h, hd = q.shape
    n_rep = h // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = softmax_scale(cfg, hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is None:
        mask = causal_mask(sq, k.shape[1], window=cfg.sliding_window,
                           q_offset=q_offset)
    if start is not None:
        pad_ok = jnp.arange(k.shape[1])[None, :] >= start[:, None]  # (B,Skv)
        mask = (mask[None] if mask.ndim == 2 else mask) & pad_ok[:, None, :]
    logits = jnp.where(mask[None, None] if mask.ndim == 2 else mask[:, None],
                       logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class KVCache:
    """KV cache; ``ring`` is static metadata (not a traced leaf) so caches
    can be scanned over the layer axis."""

    def __init__(self, k, v, length, ring: bool = False):
        self.k = k            # (B, S_cache, KV, hd) — S_cache = S_max or W
        self.v = v
        self.length = length  # () int32: tokens written so far (absolute)
        self.ring = bool(ring)

    def tree_flatten(self):
        return (self.k, self.v, self.length), self.ring

    @classmethod
    def tree_unflatten(cls, ring, children):
        return cls(*children, ring=ring)

    @classmethod
    def init(cls, cfg: ModelConfig, batch: int, max_len: int,
             dtype=jnp.bfloat16) -> "KVCache":
        w = cfg.sliding_window
        s = min(max_len, w) if (w is not None and w < max_len) else max_len
        kvh = cfg.num_kv_heads * max(1, cfg.decode_kv_expand)
        shape = (batch, s, kvh, cfg.head_dim)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32), ring=bool(w is not None and w < max_len))


def _expand_heads(k_new, kv_stored: int):
    """OPT(decode_cache): the cache may store each KV head ``e`` times (so
    stored heads == TP degree and attention shards losslessly); expand the
    incoming head dim (axis 2 of (B,S,KV,hd)) to match."""
    kv_n = k_new.shape[2]
    if kv_stored == kv_n:
        return k_new
    assert kv_stored % kv_n == 0, (kv_stored, kv_n)
    return jnp.repeat(k_new, kv_stored // kv_n, axis=2)


def _expand_to_cache(cache: KVCache, k_new):
    return _expand_heads(k_new, cache.k.shape[2])


def _pool_heads(pool, hd: int) -> int:
    """KV heads a page pool stores: ``(L, NP, PS, KV, hd)``, or ``(L, NP,
    PS, KV*hd)`` where a head is narrower than a lane tile."""
    return math.prod(pool.shape[3:]) // hd


def _pool_rows(pool, rows):
    """K or V ``(..., KV, hd)`` in the pool's layout of a token's heads."""
    return rows.reshape(rows.shape[:-2] + pool.shape[3:])


def cache_update_decode(cache: KVCache, k_new, v_new) -> KVCache:
    """Append ONE token (k_new/v_new: (B,1,KV,hd))."""
    k_new = _expand_to_cache(cache, k_new)
    v_new = _expand_to_cache(cache, v_new)
    s_cache = cache.k.shape[1]
    pos = jnp.where(cache.ring, cache.length % s_cache,
                    jnp.minimum(cache.length, s_cache - 1))
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                     (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                     (0, pos, 0, 0))
    return KVCache(k, v, cache.length + 1, cache.ring)


def decode_attention(cfg: ModelConfig, q, cache: KVCache,
                     start: Optional[jax.Array] = None) -> jax.Array:
    """One-token attention against the cache. q: (B,1,H,hd).

    The cache position of the current token must already be written
    (call :func:`cache_update_decode` first). Works for both layouts:
    for the ring cache, positions are validated modulo the window.

    ``start`` — (B,) int32 — marks each row's first valid cache slot: the
    serve engine left-pads mixed-length prompts (and admits new requests
    mid-stream at ``cur - plen``), so slots below ``start[b]`` hold pad or
    stale K/V and must not be attended. Full-cache layout only (the ring
    cache re-uses slots, so a per-row start offset is not meaningful there;
    the engine batches ring archs by equal prompt length instead).
    """
    b, _, h, hd = q.shape
    s_cache = cache.k.shape[1]
    n_rep = h // cache.k.shape[2]
    # OPT(kv_fp8): the cache may be stored in float8_e4m3fn (half the HBM
    # traffic of bf16 — the dominant decode roofline term); dequantize to
    # the compute dtype at read.
    k = _repeat_kv(cache.k, n_rep).astype(q.dtype)
    v = _repeat_kv(cache.v, n_rep).astype(q.dtype)
    scale = softmax_scale(cfg, hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    # validity: slot i holds absolute position p(i); valid iff p(i) <= cur.
    idx = jnp.arange(s_cache)
    cur = cache.length  # tokens written INCLUDING the current one
    if cache.ring:
        # slot i holds the latest absolute position congruent to i (mod S).
        valid = jnp.broadcast_to(idx < jnp.minimum(cur, s_cache),
                                 (b, s_cache))
    else:
        valid = jnp.broadcast_to(idx < cur, (b, s_cache))
        if start is not None:
            valid = valid & (idx[None, :] >= start[:, None])
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# paged KV cache: fixed page pool + per-slot page table
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class PagedKVCache:
    """Layer-stacked paged KV cache.

    Instead of one contiguous ``(L, B, S_max, KV, hd)`` buffer, K/V live in
    a fixed pool of fixed-size pages ``(L, num_pages, page_size, KV, hd)``
    with a per-slot page table ``(B, max_pages)`` mapping each slot's
    logical page (virtual position ``p`` -> logical page ``p // page_size``)
    to a pool page, ``-1`` = unmapped. Pool page 0 is the engine's TRASH
    page: writes routed through an unmapped table entry (pad prefix,
    finished slots) land there and are never validly read — attention masks
    by ``[start, length)`` exactly as on the contiguous cache, so the two
    layouts are token-identical by construction.

    The table is shared by every layer (one allocation covers the whole
    stack); ``page_size`` is static metadata so the pool can ride in a
    layer scan's carry. Allocation lives in :mod:`repro.serve.paging`.
    """

    def __init__(self, k, v, table, length, page_size: int):
        self.k = k                # (L, NP, PS, KV, hd)
        self.v = v
        self.table = table        # (B, MAXP) int32
        self.length = length      # () int32 — absolute write cursor
        self.page_size = int(page_size)

    def tree_flatten(self):
        return (self.k, self.v, self.table, self.length), self.page_size

    @classmethod
    def tree_unflatten(cls, page_size, children):
        return cls(*children, page_size=page_size)


@jax.tree_util.register_pytree_node_class
class PagedKVLayer:
    """Layer ``layer``'s view of a :class:`PagedKVCache` — what the
    per-layer block code sees in place of a :class:`KVCache`.

    It names the whole layer-stacked pool and a layer index (a traced
    scalar inside the layer scan, a Python int in the unrolled stacks):
    writes scatter into the stacked pool and the gather reads it where it
    lies, so the layer loop carries one pool buffer that XLA updates in
    place, and no layer's pool is ever sliced out or copied."""

    def __init__(self, k, v, table, length, layer, page_size: int):
        self.k = k                # (L, NP, PS, KV, hd) — the stacked pool
        self.v = v
        self.table = table        # (B, MAXP) int32
        self.length = length      # () int32
        self.layer = layer        # () int32 — which layer of the pool
        self.page_size = int(page_size)

    def tree_flatten(self):
        return ((self.k, self.v, self.table, self.length, self.layer),
                self.page_size)

    @classmethod
    def tree_unflatten(cls, page_size, children):
        return cls(*children, page_size=page_size)


def _paged_write_ids(table, pos, page_size):
    """Pool page ids for writing virtual position(s) ``pos`` per slot;
    unmapped entries route to the trash page (0)."""
    ids = jnp.take(table, pos // page_size, axis=1)   # (B,) or (B, n)
    return jnp.where(ids >= 0, ids, 0)


def paged_update_decode(layer: PagedKVLayer, k_new, v_new) -> PagedKVLayer:
    """Append ONE token (k_new/v_new: (B,1,KVn,hd)) at the shared cursor.

    Every slot writes row ``[layer, table[b, cur // PS], cur % PS]`` of the
    stacked pool — distinct pages by the allocator's unique-ownership
    invariant, so the scatter never collides (except in the trash page,
    whose content is never read)."""
    ps = layer.page_size
    heads = _pool_heads(layer.k, k_new.shape[-1])
    k_new = _pool_rows(layer.k, _expand_heads(k_new, heads))
    v_new = _pool_rows(layer.k, _expand_heads(v_new, heads))
    pos = layer.length
    ids = _paged_write_ids(layer.table, pos[None], ps)[:, 0]  # (B,)
    off = pos % ps
    l = layer.layer
    k = layer.k.at[l, ids, off].set(k_new[:, 0].astype(layer.k.dtype))
    v = layer.v.at[l, ids, off].set(v_new[:, 0].astype(layer.v.dtype))
    return PagedKVLayer(k, v, layer.table, layer.length + 1, l, ps)


def paged_prefill_update(layer: PagedKVLayer, k_new, v_new) -> PagedKVLayer:
    """Write a fresh prefill (k_new/v_new: (B,S,KVn,hd)) at positions
    ``[0, S)`` — whole pages scattered into the layer's pages of the
    stacked pool; positions whose pages are unmapped (each slot's left-pad
    prefix) go to the trash page."""
    ps = layer.page_size
    heads = _pool_heads(layer.k, k_new.shape[-1])
    k_new = _expand_heads(k_new, heads)
    v_new = _expand_heads(v_new, heads)
    b, s = k_new.shape[:2]
    npg = -(-s // ps)
    pad = npg * ps - s
    if pad:
        k_new = jnp.pad(k_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_new = jnp.pad(v_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kp = k_new.reshape((b, npg, ps) + layer.k.shape[3:])
    vp = v_new.reshape((b, npg, ps) + layer.v.shape[3:])
    ids = layer.table[:, :npg]
    ids = jnp.where(ids >= 0, ids, 0)                 # (B, npg)
    l = layer.layer
    k = layer.k.at[l, ids].set(kp.astype(layer.k.dtype))
    v = layer.v.at[l, ids].set(vp.astype(layer.v.dtype))
    return PagedKVLayer(k, v, layer.table, layer.length + s, l, ps)


def paged_splice(cache: PagedKVCache, slot, dest, k_rows, v_rows
                 ) -> PagedKVCache:
    """Admission splice: write ``k_rows``/``v_rows`` (``(L, S, KV, hd)``)
    into ``slot``'s pages at virtual positions ``[dest, dest + S)``,
    page-table-indirect and not page-aligned (positions below the admitted
    request's ``start`` fall through unmapped entries to the trash page)."""
    ps = cache.page_size
    ll, np_ = cache.k.shape[:2]
    tail = cache.k.shape[3:]
    s = k_rows.shape[1]
    pos = jnp.asarray(dest, jnp.int32) + jnp.arange(s, dtype=jnp.int32)
    row = jnp.take(cache.table, jnp.asarray(slot, jnp.int32), axis=0)
    ids = jnp.take(row, pos // ps)
    ids = jnp.where(ids >= 0, ids, 0)
    flat = ids * ps + pos % ps                        # (S,)
    k = cache.k.reshape((ll, np_ * ps) + tail)
    v = cache.v.reshape((ll, np_ * ps) + tail)
    k_rows = k_rows.reshape(k_rows.shape[:2] + tail)
    v_rows = v_rows.reshape(v_rows.shape[:2] + tail)
    k = k.at[:, flat].set(k_rows.astype(k.dtype)).reshape(cache.k.shape)
    v = v.at[:, flat].set(v_rows.astype(v.dtype)).reshape(cache.v.shape)
    return PagedKVCache(k, v, cache.table, cache.length, ps)


def paged_decode_attention(cfg: ModelConfig, q, layer: PagedKVLayer,
                           start: Optional[jax.Array] = None) -> jax.Array:
    """One-token attention against the paged cache, over ``[start,
    length)`` as on the contiguous layout. On TPU the Pallas kernel of
    :mod:`repro.kernels.paged_kv` reads each slot's live pages where they
    lie in the stacked pool; elsewhere each slot's pages of this layer are
    gathered into sequence order for the standard masked decode
    attention."""
    from repro.kernels import paged_kv
    hd = q.shape[-1]
    if paged_kv._on_tpu():
        o = paged_kv.paged_decode_attention_pallas(
            q[:, 0], layer.k, layer.v, layer.table, start, layer.length,
            layer.layer, scale=softmax_scale(cfg, hd))
        return o[:, None]
    heads = (-1, hd)  # a merged row of heads parts again
    k_view = paged_kv.paged_gather_take(layer.k, layer.table, layer.layer)
    v_view = paged_kv.paged_gather_take(layer.v, layer.table, layer.layer)
    k_view = k_view.reshape(k_view.shape[:2] + heads)
    v_view = v_view.reshape(v_view.shape[:2] + heads)
    view = KVCache(k_view, v_view, layer.length, ring=False)
    return decode_attention(cfg, q, view, start=start)


# ---------------------------------------------------------------------------
# flash-decode partial-softmax combine (beyond-paper: used when the KV cache
# sequence is sharded across the mesh — the long_500k layout)
# ---------------------------------------------------------------------------

def partial_attention(q, k, v, valid) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Attention over a sequence SHARD; returns (out, max, sum-exp) so shards
    combine exactly: the standard flash-decode two-pass-free reduction."""
    hd = q.shape[-1]
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)                 # (B,H,Q,1)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
    return out, m, l


def combine_partials(outs, ms, ls):
    """Combine per-shard (out, m, l) triples along a new leading axis."""
    m_glob = jnp.max(ms, axis=0)                                # (B,H,Q,1)
    alpha = jnp.exp(ms - m_glob)                                # (N,B,H,Q,1)
    l_glob = jnp.sum(ls * alpha, axis=0)
    # out: (N,B,Q,H,hd); alpha is (N,B,H,Q,1) -> transpose to (N,B,Q,H,1)
    alpha_o = jnp.transpose(alpha, (0, 1, 3, 2, 4))
    out = jnp.sum(outs.astype(jnp.float32) * alpha_o, axis=0)
    l_o = jnp.transpose(l_glob, (0, 2, 1, 3))                   # (B,Q,H,1)
    return (out / jnp.maximum(l_o, 1e-30)).astype(outs.dtype)
