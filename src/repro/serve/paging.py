"""Page allocator for the paged KV cache (serve engine).

The paged serve cache is a fixed pool of fixed-size pages plus a per-slot
page table (:class:`repro.models.attention.PagedKVCache`). This module owns
the *allocation* half of that design: a pure-JAX free-page allocator whose
state is two small int32 arrays, so every operation jits (and round-trips
through jit — the engine calls the jitted forms between decode steps
without ever synchronizing) and the arrays ride along with donated caches.

State (:class:`PageState`):

* ``table`` — ``(B, max_pages)`` int32: slot b's logical page ``p`` lives in
  pool page ``table[b, p]``; ``-1`` means unmapped (reads/writes through an
  unmapped entry are routed to the reserved trash page — see below);
* ``owner`` — ``(num_pages,)`` int32: the slot owning each pool page, ``-1``
  free, ``OWNER_RESERVED`` never allocatable.

Pool page 0 is the TRASH page (``owner[0] = OWNER_RESERVED``): finished
slots' decode writes and pad-prefix prefill writes land there, so the model
code never needs a branch for "this row has no page" — attention masks the
positions anyway. Allocation picks the LOWEST free pool ids (``jnp.nonzero``
order), which keeps the realized mapping deterministic: a request's tokens
must not depend on *which* pages its slot got, and the tests pin that
determinism.

Capacity is the CALLER's contract: the engine reserves worst-case page
spans at batch formation / admission time, so ``alloc`` never runs out.
Each op still returns an ``ok`` flag (enough free pages existed); on
overflow the surplus updates are dropped (out-of-bounds scatter) and ``ok``
is False — callers that can't pre-reserve must check it.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

OWNER_FREE = -1
OWNER_RESERVED = -2
TRASH_PAGE = 0


class PageState(NamedTuple):
    """Allocator state; both leaves are small int32 arrays (jit-friendly)."""

    table: jax.Array   # (B, max_pages) int32 — pool page id or -1
    owner: jax.Array   # (num_pages,) int32 — owning slot, -1 free, -2 reserved


def page_state_init(num_pages: int, batch: int, max_pages: int) -> PageState:
    """Fresh state: everything unmapped, page 0 reserved as trash."""
    if num_pages < 2:
        raise ValueError(f"need >= 2 pages (1 is the trash page), got "
                         f"{num_pages}")
    table = jnp.full((batch, max_pages), -1, jnp.int32)
    owner = jnp.full((num_pages,), OWNER_FREE, jnp.int32)
    owner = owner.at[TRASH_PAGE].set(OWNER_RESERVED)
    return PageState(table, owner)


def pages_free(state: PageState) -> jax.Array:
    """() int32 — allocatable pages remaining."""
    return jnp.sum((state.owner == OWNER_FREE).astype(jnp.int32))


def pages_used(state: PageState) -> jax.Array:
    """() int32 — pages currently owned by some slot (trash excluded)."""
    return jnp.sum((state.owner >= 0).astype(jnp.int32))


def _take_free(owner: jax.Array, n: int) -> Tuple[jax.Array, jax.Array]:
    """(ids: (n,) int32 lowest free pool pages, ok: () bool).

    On shortfall the missing ids are ``num_pages`` (one past the pool), so
    the subsequent scatters drop them instead of corrupting page state.
    """
    free = owner == OWNER_FREE
    ids = jnp.nonzero(free, size=n, fill_value=owner.shape[0])[0]
    ids = ids.astype(jnp.int32)
    ok = jnp.sum(free.astype(jnp.int32)) >= n
    return ids, ok


def alloc_slot_pages(state: PageState, slot: jax.Array,
                     logical: jax.Array) -> Tuple[PageState, jax.Array]:
    """Map ``len(logical)`` fresh pool pages at ``slot``'s logical indices.

    ``logical`` — (n,) int32, n static. Returns (new state, ok). Used for
    the initial-prefill and admission-prefill ranges.

    Contract: every ``logical`` entry must currently be UNMAPPED for
    ``slot`` — remapping a mapped entry overwrites the table reference
    while the old page keeps its owner, leaking it until the next
    ``free_slot_pages``. The engine satisfies this by freeing a slot
    before re-admitting into it.
    """
    n = logical.shape[0]
    ids, ok = _take_free(state.owner, n)
    owner = state.owner.at[ids].set(jnp.asarray(slot, jnp.int32))
    # shortfall ids are out of range: the owner scatter drops them and the
    # table keeps those logical entries unmapped — a failed alloc leaves a
    # consistent (partially mapped) state
    table_ids = jnp.where(ids < state.owner.shape[0], ids, -1)
    table = state.table.at[jnp.asarray(slot, jnp.int32),
                           logical].set(table_ids)
    return PageState(table, owner), ok


def alloc_step_pages(state: PageState, slots: jax.Array,
                     logical: jax.Array) -> Tuple[PageState, jax.Array]:
    """One page per slot in ``slots`` at the SAME logical index — the decode
    page-boundary allocation (the shared write cursor crosses into logical
    page ``cur // page_size`` for every live slot at once).

    ``slots`` — (m,) int32, m static; ``logical`` — () int32. Same
    unmapped-entry contract as :func:`alloc_slot_pages`.
    """
    m = slots.shape[0]
    ids, ok = _take_free(state.owner, m)
    owner = state.owner.at[ids].set(slots.astype(jnp.int32))
    table_ids = jnp.where(ids < state.owner.shape[0], ids, -1)
    table = state.table.at[slots.astype(jnp.int32),
                           jnp.asarray(logical, jnp.int32)].set(table_ids)
    return PageState(table, owner), ok


def free_slot_pages(state: PageState, slot: jax.Array) -> PageState:
    """Reclaim every page ``slot`` owns and clear its table row — the
    per-slot compaction the paged cache gets for free: the instant a
    request finishes, its pages return to the pool."""
    slot = jnp.asarray(slot, jnp.int32)
    owner = jnp.where(state.owner == slot, OWNER_FREE, state.owner)
    table = state.table.at[slot].set(-1)
    return PageState(table, owner)


def pages_for_span(start: int, end: int, page_size: int) -> int:
    """Host-side: pages covering token positions ``[start, end)`` — the
    engine's reservation unit (worst-case span of one slot)."""
    if end <= start:
        return 0
    return (end - 1) // page_size - start // page_size + 1


# jitted forms — the engine uses these between decode steps; shapes key the
# trace cache (n distinct range sizes / live-slot counts stay small).
alloc_slot_pages_jit = jax.jit(alloc_slot_pages)
alloc_step_pages_jit = jax.jit(alloc_step_pages)
free_slot_pages_jit = jax.jit(free_slot_pages)
