"""Paged KV cache: decode attention over live pages, and the page gather.

The paged serve cache stores K/V in a fixed pool of fixed-size pages
(``(L, num_pages, page_size, KV, hd)``, stacked over layers, or ``(L,
num_pages, page_size, KV*hd)`` where a head is narrower than a lane tile)
with a per-slot page table shared by every layer (``-1`` unmapped: the pad
prefix and finished slots).

* :func:`paged_decode_attention_pallas` — the TPU kernel of the decode
  step: one query token per slot attends over that slot's live pages of
  one layer, read where they lie in the stacked pool (interpret-mode tested
  on CPU);
* :func:`paged_gather_take` — one layer's pages of each slot in sequence
  order, by one row gather (the CPU lowering of the decode step, followed
  by the contiguous decode attention, and the kernel's oracle);
* :func:`paged_gather_ref` — scalar oracle of the gather.

:func:`_on_tpu` decides which of the two the model code takes.
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
# a compute block holds at least this many tokens, and its K (or V) pages
# about BLOCK_BYTES, double-buffered in VMEM
BLOCK_TOKENS = 128
BLOCK_BYTES = 1 << 20


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _block_pages(page_size: int, page_bytes: int, max_pages: int) -> int:
    """Pages a compute block of the kernel holds: at least
    ``BLOCK_TOKENS`` tokens and about ``BLOCK_BYTES`` of K a buffer, never
    more than a slot's table row."""
    p = max(-(-BLOCK_TOKENS // page_size), BLOCK_BYTES // page_bytes)
    return max(1, min(p, max_pages))


def _kernel(layer_ref, table_ref, start_ref, length_ref,
            q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, live_ref, *,
            scale: float, groups: int, hd: int, block: int):
    b_n, h, _ = q_ref.shape
    maxp = table_ref.shape[0] // b_n
    ps = k_hbm.shape[2]
    w = k_hbm.shape[-1]                      # a pool row: hd or KV*hd
    rows_tok = math.prod(k_hbm.shape[3:]) // w   # pool rows a token holds
    per_row = w // hd                        # heads a pool row holds
    n = block * ps * rows_tok                # rows of a compute block
    layer = layer_ref[0]
    # every span ends at the cursor: positions [lo_b, hi), pages up to
    # hi_page (the scalar operands are never negative: lax.div truncates)
    hi = jnp.minimum(length_ref[0], maxp * ps)
    hi_page = jax.lax.div(hi + ps - 1, ps)

    def lo(b):
        return jnp.maximum(start_ref[b], 0)

    def blocks(b):
        first = jax.lax.div(lo(b), ps)
        pages = jnp.where(hi > lo(b), hi_page - first, 0)
        return jax.lax.div(pages + block - 1, block)

    def next_slot(b):
        """The first slot from b on with a block to attend, or B."""
        return jax.lax.while_loop(
            lambda c: jnp.logical_and(
                c < b_n, blocks(jnp.minimum(c, b_n - 1)) == 0),
            lambda c: c + 1, b)

    def each_page(b, i, fn, carry):
        """Folds ``fn(k, pool page id, in span, carry)`` over the pages of
        slot b's block i. A page is live where it lies in the span and is
        mapped (its id is not negative)."""
        first = jax.lax.div(lo(b), ps) + i * block
        row, in_span = b * maxp + first, jnp.minimum(hi_page - first, block)
        for k in range(block):
            pid = table_ref[row + jnp.minimum(k, in_span - 1)]
            carry = fn(k, pid, k < in_span, carry)
        return carry

    def dmas(k, pid, buf):
        pid = jnp.maximum(pid, 0)
        return (pltpu.make_async_copy(k_hbm.at[layer, pid], kbuf.at[buf, k],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, pid], vbuf.at[buf, k],
                                      sems.at[1, buf]))

    def start(b, i, buf):
        def one(k, pid, inside, c):
            @pl.when(inside & (pid >= 0))
            def _():
                for d in dmas(k, pid, buf):
                    d.start()
            return c
        each_page(b, i, one, 0)

    def wait(b, i, buf):
        """Waits for block i's copies; returns whether any of its pages is
        live, and whether every page of it in the span is mapped."""
        def one(k, pid, inside, c):
            live = inside & (pid >= 0)

            @pl.when(live)
            def _():
                for d in dmas(k, pid, buf):
                    d.wait()
            any_live, mapped = c
            return any_live | live, mapped & (live | ~inside)
        return each_page(b, i, one, (jnp.bool_(False), jnp.bool_(True)))

    def mark_live(b, i):
        """Block i's rows on live pages, (1, n): read where an unmapped
        page lies inside the span."""
        row_page = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // (
            ps * rows_tok)
        live_ref[...] = each_page(
            b, i, lambda k, pid, inside, m: jnp.where(
                row_page == k, (inside & (pid >= 0)).astype(jnp.int32), m),
            jnp.zeros((1, n), jnp.int32))

    def attend(b, i, buf, mapped):
        row = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        pos = (jax.lax.div(lo(b), ps) + i * block) * ps + row // rows_tok
        live = (pos >= lo(b)) & (pos < hi) & (
            live_ref[...] + mapped.astype(jnp.int32) > 0)     # (1, n)
        # a query head j reads the pool row and the head within it that
        # hold its KV head j // groups
        head = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0) // groups
        own = (row % rows_tok) == head // per_row             # (h, n)
        dt = q_ref.dtype
        k = kbuf[buf].reshape(n, w).astype(dt)
        v = vbuf[buf].reshape(n, w).astype(dt)
        s = jax.lax.dot_general(q_ref[b], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = live & own
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a live page holds a position of the span, so every head has a
        # score in the block, m_new is finite and the masked ones give 0
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(dt), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def finish(b):
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # (h, w)
        if per_row > 1:
            # head j's output lies in lanes of its KV head within the row
            r = (jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0) // groups
                 ) % per_row
            out = sum(jnp.where(r == i, out[:, i * hd:(i + 1) * hd], 0.0)
                      for i in range(per_row))
        o_ref[b] = out.astype(o_ref.dtype)

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    # a buffer's rows hold zeros or pool rows, never unset memory: a row
    # outside the live span then meets a zero probability with a finite V
    kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
    vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
    live_ref[...] = jnp.zeros(live_ref.shape, live_ref.dtype)
    first = next_slot(0)

    @pl.when(first < b_n)
    def _():
        start(first, 0, 0)

    def step(c):
        b, i, buf = c
        last = i + 1 >= blocks(b)
        nb = jax.lax.cond(last, lambda: next_slot(b + 1), lambda: b)
        ni = jnp.where(last, 0, i + 1)

        @pl.when(nb < b_n)
        def _():
            start(nb, ni, 1 - buf)

        any_live, mapped = wait(b, i, buf)

        @pl.when(any_live & ~mapped)
        def _():
            mark_live(b, i)

        @pl.when(i == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @pl.when(any_live)
        def _():
            attend(b, i, buf, mapped)

        @pl.when(last)
        def _():
            finish(b)
        return nb, ni, 1 - buf

    jax.lax.while_loop(lambda c: c[0] < b_n, step,
                       (first, jnp.int32(0), jnp.int32(0)))


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, table: jax.Array,
                                  start: Optional[jax.Array], length, layer,
                                  *, scale: float,
                                  interpret: bool = False) -> jax.Array:
    """One query token per slot against its live pages of one layer.

    q: ``(B, H, hd)``; k_pool/v_pool: the layer-stacked pools, ``(L, NP,
    PS, KV, hd)`` or ``(L, NP, PS, KV*hd)``; table: ``(B, MAXP)`` int32
    pool page ids (``-1`` unmapped); start: ``(B,)`` int32 each slot's
    first valid position (None: 0); length: () int32 the shared cursor,
    the current token included; layer: () int32. Returns ``(B, H, hd)``
    in q's dtype.

    A page is live where its table entry is mapped and it overlaps ``[start,
    length)``; slot b attends to the positions of ``[start[b], length)`` on
    live pages, which is what the contiguous decode attention attends to
    over the gathered pages wherever the slot's span is mapped. One call
    walks every slot's live pages in compute blocks of
    :func:`_block_pages` pages: a copy per live page straight from the
    stacked pool into VMEM, double-buffered across blocks and slots, and
    none for any other page. K and V are cast to q's dtype after the read;
    the scores are f32 and scaled by ``scale``; the online softmax keeps
    its max, denominator and output in VMEM; the probabilities are cast to
    q's dtype for the weighted sum. A slot with no live page returns zeros.
    The H // KV query heads of a KV head read its pages once: the scores
    of every query head against every row are one matmul, and a mask keeps
    each head to its own KV head's row (``(KV, hd)`` pools) or lanes
    (``KV*hd`` rows, whose queries arrive spread over the row's width).
    """
    b, h, hd = q.shape
    maxp = table.shape[1]
    ps = k_pool.shape[2]
    tail = k_pool.shape[3:]
    w = tail[-1]
    kv = math.prod(tail) // hd
    groups = h // kv
    per_row = w // hd
    if per_row > 1:
        # spread each query head over the row at its KV head's lanes
        r = (jnp.arange(h) // groups) % per_row
        sel = (r[:, None] == jnp.arange(per_row)[None, :]).astype(q.dtype)
        q = (q[:, :, None, :] * sel[None, :, :, None]).reshape(b, h, w)
    page_bytes = ps * math.prod(tail) * jnp.dtype(k_pool.dtype).itemsize
    block = _block_pages(ps, page_bytes, maxp)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    kernel = functools.partial(_kernel, scale=float(scale), groups=groups,
                               hd=hd, block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, h, w), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b, h, hd), lambda i, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block, ps) + tail, k_pool.dtype),
            pltpu.VMEM((2, block, ps) + tail, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, w), jnp.float32),
            pltpu.VMEM((1, block * ps * (math.prod(tail) // w)), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), table.reshape(-1),
      jnp.asarray(start, jnp.int32), jnp.asarray(length, jnp.int32).reshape(1),
      q, k_pool, v_pool)


def paged_gather_take(pool: jax.Array, table: jax.Array, layer
                      ) -> jax.Array:
    """Slot b's pages of ``layer`` in logical order, ``(B, MAXP*PS) +
    pool.shape[3:]``: ONE row gather from the stacked pool plus an
    unmapped-page mask (unmapped pages read as zeros)."""
    b, maxp = table.shape
    ps = pool.shape[2]
    ids = jnp.clip(table, 0, pool.shape[1] - 1)
    pages = pool[layer, ids]                          # (B, MAXP, PS, KV, hd)
    mapped = (table >= 0).reshape((b, maxp) + (1,) * (pages.ndim - 2))
    pages = jnp.where(mapped, pages, jnp.zeros((), pool.dtype))
    return pages.reshape((b, maxp * ps) + pool.shape[3:])


def paged_gather_ref(pool, table) -> jax.Array:
    """Scalar jnp oracle of :func:`paged_gather_take`; ``pool`` is one
    layer's ``(NP, PS, KV, hd)`` pool."""
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
