"""Serving: prefill + batched decode with KV/SSM caches.

``make_serve_step`` builds the one-token decode function the dry-run lowers
for the decode shapes (``decode_32k``, ``long_500k``): ONE new token against
a ``seq_len``-deep cache. With a :class:`~repro.serve.comm.ServeCommPlan`
it instead builds the manual-TP step whose collectives (attention/FFN
partial sums, MoE combine, vocab-parallel sampling gather) each ride their
own CommContext/VCI stream — the serve-side analogue of the gradient
bucketing path.

``ServeEngine`` is the host-side continuous-batching loop over the PAGED
decode cache (:class:`~repro.models.attention.PagedKVCache` + the pure-JAX
allocator in :mod:`repro.serve.paging`):

* mixed-length prompts are LEFT-padded to a common width and prefilled with
  per-row pad masks + shifted RoPE positions, so a request's tokens are
  identical no matter what it is batched with;
* greedy or per-request temperature sampling, per-request ``stop_token``
  and ``max_new_tokens``;
* a finished slot's pages return to the pool the moment it finishes, and
  the slot is re-filled mid-stream by prefilling the next request into
  freshly allocated pages just below the shared write cursor (its ``start``
  offset masks everything older) — on one device or under a mesh, with the
  running batch's TP specs;
* ``generate()`` validates ``prompt_len + max_new_tokens <= max_len`` and
  each request's page span against the pool up front — decode can never
  write past the cache depth or run out of pages.

The continuous loop never waits on the device before it dispatches the
next decode step: it reads each step's tokens one step late, while the
following step runs. Everything it decides before a dispatch is known on
the host without a read — a slot finishes its token budget at a step fixed
by the budget, so the cursor of every admission and the live slots at
every page boundary are too. The step's token input stays on the device
(the previous step's output, with each admission's first token written
into its row), and the allocator's ``ok`` flags are read with the next
token read. Reads go through a snapshot of which request each row served
at dispatch, since a slot may hold another request by the time its old
tokens arrive. Only a stop token is seen one step late: the slot runs one
extra step inside its own reservation (see ``_take_batch``), whose token
is dropped, and is freed when the stop is read.

Text models of one mixer per layer with no ring cache have a paged layout
(:func:`~repro.models.transformer.has_paged_layout`): dense, MoE, SSM and
the per-layer pattern hybrids (granite-4.0-h), whose Mamba-2 layers keep a
recurrent state per slot beside the attention layers' pages. Left-pad
positions give a Mamba-2 layer no step, and admission writes the slot's
state row. Ring caches, zamba2's shared attention block and the VLM/audio
frontends have none and fall back to equal-length grouped batches on the
contiguous cache — same results, no corruption, just less packing.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.sharding import Sharder, batch_axes
from repro.models.attention import KVCache, PagedKVCache, paged_splice
from repro.models.ssm import SSMState
from repro.models.transformer import (
    DecodeCache,
    Model,
    has_paged_layout,
    init_cache,
    init_paged_cache,
)
from repro.serve.comm import (
    TP_AXIS,
    ServeCommPlan,
    serve_cache_specs,
    serve_param_specs,
    serve_tp_validate,
)
from repro.serve.paging import (
    PageState,
    alloc_slot_pages_jit,
    alloc_step_pages_jit,
    free_slot_pages_jit,
    page_state_init,
    pages_for_span,
)


def greedy_sample(logits: jax.Array) -> jax.Array:
    """logits: (B, 1, V) or (B, K, 1, V) -> next token ids."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature_sample(key, logits, temperature: float = 1.0):
    return jax.random.categorical(key, logits / max(temperature, 1e-4)
                                  ).astype(jnp.int32)


def select_tokens(logits, temps=None, key=None) -> jax.Array:
    """Greedy/temperature sampling with PER-ROW temperatures.

    ``temps`` — (B,) float32; rows with ``temp <= 0`` take the argmax, rows
    with ``temp > 0`` sample from the tempered categorical. ``temps=None``
    is pure greedy (and needs no key). logits: (B, 1, V) or (B, K, 1, V).
    """
    greedy = greedy_sample(logits)
    if temps is None:
        return greedy
    if key is None:
        raise ValueError("select_tokens: temps given without a PRNG key — "
                         "pass key=... or temps=None for greedy")
    b = logits.shape[0]
    t = temps.reshape((b,) + (1,) * (logits.ndim - 1 - 1))
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(t, 1e-4)[..., None]).astype(jnp.int32)
    use = (temps > 0).reshape((b,) + (1,) * (greedy.ndim - 1))
    return jnp.where(use, sampled, greedy)


def _last_logits(cfg: ModelConfig, logits):
    if cfg.modality == "audio":
        return logits[..., -1:, :]
    return logits[:, -1:, :]


def make_serve_step(cfg: ModelConfig, mesh=None, comm_plan=None, lane: int = 0
                    ) -> Callable[..., Tuple]:
    """Returns ``serve_step(params, tokens, cache, start=None, temps=None,
    key=None) -> (next_tokens, cache)``.

    tokens: (B,1) int32 (or (B,K,1) audio). This is the function the decode
    dry-run shapes lower. ``comm_plan`` selects the manual-TP VCI-stream
    path (see :mod:`repro.serve.comm`).
    """
    if comm_plan is not None:
        return _make_serve_step_comm(cfg, mesh, comm_plan, lane)
    shard = Sharder(mesh, cfg) if mesh is not None else None
    model = Model(cfg, shard)

    def serve_step(params, tokens, cache: DecodeCache, start=None,
                   temps=None, key=None):
        logits, new_cache = model.decode_step(params, tokens, cache,
                                              start=start)
        nxt = select_tokens(logits, temps, key)
        return nxt, new_cache

    return serve_step


def make_prefill(cfg: ModelConfig, mesh=None, comm_plan=None, lane: int = 0):
    """Returns ``prefill(params, batch, cache, start=None, temps=None,
    key=None) -> (next_tokens, cache)`` sampling the first new token."""
    if comm_plan is not None:
        return _make_prefill_comm(cfg, mesh, comm_plan, lane)
    shard = Sharder(mesh, cfg) if mesh is not None else None
    model = Model(cfg, shard)

    def prefill(params, batch, cache: DecodeCache, start=None, temps=None,
                key=None):
        logits, _, new_cache = model.forward(params, batch, cache=cache,
                                             start=start)
        nxt = select_tokens(_last_logits(cfg, logits), temps, key)
        return nxt, new_cache

    return prefill


# ---------------------------------------------------------------------------
# the manual-TP (VCI stream) step builders
# ---------------------------------------------------------------------------

def _mesh_tp(mesh) -> int:
    return dict(mesh.shape).get(TP_AXIS, 1)


def _mesh_batch(mesh) -> Tuple[Any, int]:
    """(spec entry, shard count) for the batch dim over the non-TP axes."""
    dp = batch_axes(mesh)
    n = 1
    for a in dp:
        n *= dict(mesh.shape)[a]
    return (dp[0] if len(dp) == 1 else tuple(dp)), n


def _make_serve_step_comm(cfg: ModelConfig, mesh, comm_plan: ServeCommPlan,
                          lane: int):
    assert mesh is not None, "comm_plan needs a mesh with a 'model' axis"
    tp = _mesh_tp(mesh)
    serve_tp_validate(cfg, tp)
    dpe, nb = _mesh_batch(mesh)

    def serve_step(params, tokens, cache, start, temps, key):
        # the paged pool is a shared resource (any slot <-> any page): it
        # replicates over the data axes, so the batch does too.
        paged = isinstance(cache.kv, PagedKVCache)
        bd = dpe if (not paged and nb > 1
                     and tokens.shape[0] % nb == 0) else None
        nshard = nb if bd is not None else 1

        def inner(params, tokens, cache, start, temps, key):
            comm = comm_plan.comm(lane)
            model = Model(cfg, None, comm=comm)
            logits, new_cache = model.decode_step(params, tokens, cache,
                                                  start=start)
            logits = comm.drain(logits)
            return select_tokens(logits, temps, key), new_cache

        cspec = serve_cache_specs(cache, tp, nshard, batch_axis=dpe)
        f = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(serve_param_specs(cfg, params, tp), P(bd, None),
                      cspec, P(bd), P(bd), P()),
            out_specs=(P(bd, None), cspec),
            check_vma=False, axis_names=set(mesh.axis_names))
        return f(params, tokens, cache, start, temps, key)

    return serve_step


def _make_prefill_comm(cfg: ModelConfig, mesh, comm_plan: ServeCommPlan,
                       lane: int):
    assert mesh is not None, "comm_plan needs a mesh with a 'model' axis"
    tp = _mesh_tp(mesh)
    serve_tp_validate(cfg, tp)
    dpe, nb = _mesh_batch(mesh)

    def prefill(params, batch, cache, start, temps, key):
        tokens = batch["tokens"]
        paged = isinstance(cache.kv, PagedKVCache)
        bd = dpe if (not paged and nb > 1
                     and tokens.shape[0] % nb == 0) else None
        nshard = nb if bd is not None else 1

        def inner(params, batch, cache, start, temps, key):
            comm = comm_plan.comm(lane)
            model = Model(cfg, None, comm=comm)
            logits, _, new_cache = model.forward(params, batch, cache=cache,
                                                 start=start)
            logits = comm.drain(logits)
            nxt = select_tokens(_last_logits(cfg, logits), temps, key)
            return nxt, new_cache

        cspec = serve_cache_specs(cache, tp, nshard, batch_axis=dpe)
        f = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(serve_param_specs(cfg, params, tp),
                      {"tokens": P(bd, None)},
                      cspec, P(bd), P(bd), P()),
            out_specs=(P(bd, None), cspec),
            check_vma=False, axis_names=set(mesh.axis_names))
        return f(params, batch, cache, start, temps, key)

    return prefill


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    prompt: np.ndarray                    # (S,) or (K,S) token ids
    max_new_tokens: int = 32
    temperature: Optional[float] = None   # None -> engine default; 0 = greedy
    stop_token: Optional[int] = None      # finish early when sampled
    generated: Optional[np.ndarray] = None


@dataclasses.dataclass
class _Stream:
    """One request of the continuous loop, in slot ``slot``. ``issued``
    counts the tokens that dispatched programs produce for it; ``tokens``
    holds those read back so far, one step later."""
    req: Request
    slot: int
    issued: int = 1  # the prefill's first token
    tokens: List[int] = dataclasses.field(default_factory=list)
    closed: bool = False

    @property
    def spent(self) -> bool:
        """Its whole budget is dispatched: known before any read."""
        return self.issued >= self.req.max_new_tokens

    def take(self, t: int) -> bool:
        """Record a token read back; True when it is the stop token. Tokens
        after the stop (the one extra step) are dropped."""
        if self.closed:
            return False
        if self.req.stop_token is not None and t == self.req.stop_token:
            self.close()
            return True
        self.tokens.append(t)
        if len(self.tokens) >= self.req.max_new_tokens:
            self.close()
        return False

    def close(self) -> None:
        self.closed = True
        self.req.generated = np.asarray(self.tokens, np.int32)


@jax.jit
def _put_token(tokens, slot, tok):
    """Write an admission's first token ``tok`` (1, 1) into row ``slot`` of
    the step's token input (B, 1), on the device; ``slot`` is traced, so one
    program serves every slot."""
    return tokens.at[slot].set(tok[0])


_ADMIT_ALIGN = 8  # admission prompts pad to multiples of this (fewer traces)


class ServeEngine:
    """Continuous-batching serving loop (see module docstring).

    ``mesh`` + ``comm_plan`` (or ``num_vcis``) select the manual-TP decode
    whose collectives ride per-purpose VCI streams; with ``mesh=None`` the
    same loop runs single-device.

    The continuous path keeps its decode state in a fixed pool of
    ``num_pages`` pages of ``page_size`` tokens plus a per-slot page table
    (:class:`~repro.models.attention.PagedKVCache`, allocation in
    :mod:`repro.serve.paging`). A finished slot's pages return to the pool
    IMMEDIATELY, so ``num_pages`` can be sized to the live-token budget
    instead of the default ``1 + batch * ceil(max_len / page_size)``.
    The pages hold the attention layers' K/V; a Mamba-2 layer's state has
    no positions and lives beside them, one row per slot, in the same
    :class:`~repro.models.transformer.DecodeCache`. Models without a paged
    layout take the grouped equal-length fallback on their own.

    ``paged`` is an input check only: ``paged=False`` (the removed
    contiguous continuous cache) raises ``ValueError``.
    """

    def __init__(self, cfg: ModelConfig, params, *, batch_size: int,
                 max_len: int, mesh=None, cache_dtype=jnp.float32,
                 comm_plan: Optional[ServeCommPlan] = None,
                 num_vcis: Optional[int] = None, vci_policy: str = "fcfs",
                 progress: str = "hybrid", token_impl: str = "barrier",
                 temperature: float = 0.0, seed: int = 0,
                 paged: bool = True, page_size: int = 16,
                 num_pages: Optional[int] = None):
        if not paged:
            raise ValueError(
                "paged=False: the contiguous continuous cache was removed; "
                "models without a paged layout take the grouped path on "
                "their own")
        self.cfg = cfg
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self.mesh = mesh
        self.temperature = temperature
        if comm_plan is None and num_vcis is not None:
            if mesh is None or _mesh_tp(mesh) <= 1:
                raise ValueError("num_vcis needs a mesh with a 'model' axis "
                                 ">1 (the TP streams live there)")
            comm_plan = ServeCommPlan(num_vcis=num_vcis,
                                      vci_policy=vci_policy,
                                      progress=progress,
                                      token_impl=token_impl)
        self.comm_plan = comm_plan
        # both programs take the cache donated: the paged pool is written
        # in place, never copied whole
        self._prefill = jax.jit(make_prefill(cfg, mesh, comm_plan),
                                donate_argnums=(2,))
        self._step = jax.jit(make_serve_step(cfg, mesh, comm_plan),
                             donate_argnums=(2,))
        self._admit_fns: Dict[int, Callable] = {}
        self._cache_dtype = cache_dtype
        self._key = jax.random.PRNGKey(seed)
        self._nkey = 0
        # continuous batching on the page pool, or the grouped fallback
        self._padded_ok = has_paged_layout(cfg, max_len)
        self._page_size = int(page_size)
        self._max_pages = -(-max_len // self._page_size)
        self._num_pages = (1 + batch_size * self._max_pages
                           if num_pages is None else int(num_pages))
        if self._padded_ok and self._num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is the trash "
                             f"page), got {self._num_pages}")
        self.cache_bytes_resident = 0
        # decode steps of the continuous loop, and those dispatched while
        # the previous step's tokens were still unread (per ``generate``)
        self.steps = 0
        self.steps_ahead = 0
        # allocator ``ok`` flags not yet read: (flag, where it was taken)
        self._unchecked: List[Tuple[jax.Array, str]] = []
        self._reserved: Dict[int, int] = {}  # slot -> pages, in the loop

    # read by chipbench/test_chipbench_hybrid.py
    _paged = property(lambda self: self._padded_ok)

    # -- small helpers ---------------------------------------------------
    def _next_key(self):
        self._nkey += 1
        return jax.random.fold_in(self._key, self._nkey)

    def _temp_of(self, r: Request) -> float:
        return self.temperature if r.temperature is None else r.temperature

    def _validate(self, requests: List[Request]) -> None:
        for i, r in enumerate(requests):
            plen = int(r.prompt.shape[-1])
            if plen < 1:
                raise ValueError(f"request {i}: empty prompt")
            if r.max_new_tokens < 1:
                raise ValueError(f"request {i}: max_new_tokens < 1")
            if plen + r.max_new_tokens > self.max_len:
                raise ValueError(
                    f"request {i}: prompt_len {plen} + max_new_tokens "
                    f"{r.max_new_tokens} exceeds the cache depth "
                    f"(max_len={self.max_len}); decode would write past the "
                    f"cache — shorten the request or raise max_len")
            if self._padded_ok:
                need = pages_for_span(0, plen + r.max_new_tokens,
                                      self._page_size)
                if need > self._num_pages - 1:
                    raise ValueError(
                        f"request {i}: needs {need} pages alone but the "
                        f"pool holds {self._num_pages - 1} allocatable "
                        f"pages (num_pages={self._num_pages}, page_size="
                        f"{self._page_size}) — grow the pool")

    def _note_cache(self, cache: DecodeCache) -> None:
        """Track the largest resident decode-cache footprint of this
        ``generate()`` call — what a right-sized page pool saves."""
        n = 0
        for leaf in jax.tree_util.tree_leaves(cache):
            n += leaf.size * leaf.dtype.itemsize
        self.cache_bytes_resident = max(self.cache_bytes_resident, n)

    # -- public API ------------------------------------------------------
    def generate(self, requests: List[Request]) -> List[Request]:
        self._validate(requests)
        self.cache_bytes_resident = 0
        self.steps = self.steps_ahead = 0
        ctx = (jax.set_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            if self._padded_ok:
                pending = list(requests)
                while pending:
                    batch = self._take_batch(pending)
                    with TraceAnnotation("serve.batch"):
                        self._run_continuous(batch, pending)
            else:
                # grouped fallback: equal prompt lengths per batch
                groups: Dict[int, List[Request]] = {}
                for r in requests:
                    groups.setdefault(int(r.prompt.shape[-1]), []).append(r)
                for _, rs in sorted(groups.items()):
                    for i in range(0, len(rs), self.batch_size):
                        with TraceAnnotation("serve.batch"):
                            self._run_grouped(rs[i: i + self.batch_size])
        return requests

    # -- batch formation -------------------------------------------------
    def _take_batch(self, pending: List[Request]) -> List[Request]:
        """Pop up to ``batch_size`` requests whose LEFT-PADDED runway fits:
        with pad width P = max(prompt lens), every member still needs
        ``P + max_new <= max_len`` (padding consumes cache depth), and the
        members' worst-case page spans (prompt + full token budget,
        page-rounded — the reservation that keeps allocation infallible)
        must fit the pool together.

        The reservation also covers the one step a slot runs after its
        stop token, before the loop reads it. A stop on token ``k`` of a
        budget ``n`` admitted at cursor ``a`` (token ``k`` is written at
        position ``a + k``) is read after step ``a + k`` is dispatched; for
        ``k <= n - 2`` that step writes position ``a + k <= a + n - 2``,
        inside the reserved span ``[.., a + n)``, and a page it crosses into
        is one the span already counts. A stop on token ``n - 1`` coincides
        with the budget finish, which the host knows without a read, so no
        extra step runs. The slot's pages return when the stop is read, and
        only then is the slot admitted into again."""
        batch: List[Request] = []
        pad = 0
        i = 0
        while i < len(pending) and len(batch) < self.batch_size:
            r = pending[i]
            p_new = max(pad, int(r.prompt.shape[-1]))
            members = batch + [r]
            fits = (all(p_new + q.max_new_tokens <= self.max_len
                        for q in members)
                    and sum(pages_for_span(p_new - int(q.prompt.shape[-1]),
                                           p_new + q.max_new_tokens,
                                           self._page_size)
                            for q in members) <= self._num_pages - 1)
            if fits:
                batch.append(pending.pop(i))
                pad = p_new
            else:
                i += 1
        assert batch, "a validated request always fits alone"
        return batch

    # -- continuous (left-padded) path ------------------------------------
    # Host spans (``jax.profiler.TraceAnnotation``, ``serve.*``) mark each
    # piece of the loop on the profiler's clock, so a trace tells which one
    # held the device idle; ``serve.sync`` marks every device-to-host read.
    # Without a profiler recording, a span costs about a microsecond.
    def _run_continuous(self, batch: List[Request],
                        pending: List[Request]) -> None:
        cfg = self.cfg
        B = self.batch_size
        PS = self._page_size
        live: List[Optional[_Stream]] = [None] * B  # slot -> its request
        for i, r in enumerate(batch):
            live[i] = _Stream(r, i)
        plens = [int((s.req if s else batch[0]).prompt.shape[-1])
                 for s in live]
        pad = max(plens)
        tokens = np.zeros((B, pad), np.int32)
        for i, s in enumerate(live):
            tokens[i, pad - plens[i]:] = (s.req if s else batch[0]).prompt
        start = np.asarray([pad - p for p in plens], np.int32)
        temps = np.asarray([self._temp_of(s.req) if s else 0.0
                            for s in live], np.float32)
        # slot -> worst-case page span, set before its pages are mapped
        self._reserved = reserved = {}
        self._unchecked = []
        with TraceAnnotation("serve.pool_init"):
            cache = init_paged_cache(cfg, B, self.max_len, page_size=PS,
                                     num_pages=self._num_pages,
                                     dtype=self._cache_dtype)
            self._owner = page_state_init(self._num_pages, B,
                                          self._max_pages).owner
            for i, s in enumerate(live):
                if s is None:
                    continue  # empty slot: writes land in the trash page
                reserved[i] = pages_for_span(
                    int(start[i]), pad + s.req.max_new_tokens, PS)
                cache = self._palloc(cache, i, int(start[i]) // PS,
                                     (pad - 1) // PS)
            self._note_cache(cache)
        # device copies of start and temps, made again only on admission;
        # jnp.array copies, since the host edits both arrays in place
        start_d, temps_d = jnp.array(start), jnp.array(temps)
        with TraceAnnotation("serve.prefill"):
            nxt, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(tokens)}, cache,
                start_d, temps_d, self._next_key())
        # results not yet read: (token array, the request of each row when
        # it was dispatched); read after the next step is dispatched
        unread = [(nxt, tuple(live))]
        cur = pad

        def free(i: int, cache):
            """Per-slot compaction for free: the instant a slot finishes its
            pages go back to the pool (its decode writes re-route to the
            trash page through the cleared table row)."""
            st = free_slot_pages_jit(
                PageState(cache.kv.table, self._owner),
                jnp.asarray(i, jnp.int32))
            self._owner = st.owner
            reserved.pop(i)
            live[i] = None
            return self._with_table(cache, st.table)

        def read(unread, cache):
            """Read the allocator's flags and the queued tokens, and record
            each token to the request its row served at dispatch; a stop
            token frees the slot if the request still holds it."""
            checks, self._unchecked = self._unchecked, []
            for ok, where in checks:
                with TraceAnnotation("serve.sync"):
                    ok = bool(ok)
                if not ok:  # reservations make this unreachable
                    raise RuntimeError(
                        f"page pool exhausted at {where} — reservation "
                        f"accounting broken")
            for arr, rows in unread:
                with TraceAnnotation("serve.sync"):
                    toks = np.asarray(arr)
                with TraceAnnotation("serve.record"):
                    for i, s in enumerate(rows):
                        if (s is not None and s.take(int(toks[i, 0]))
                                and live[s.slot] is s):
                            cache = free(s.slot, cache)
            return cache

        while True:
            with TraceAnnotation("serve.record"):
                for i, s in enumerate(live):
                    if s is not None and s.spent:
                        cache = free(i, cache)
            # early slot recycling: prefill the next request into a finished
            # slot just below the shared cursor (start masks older rows)
            admitted = False
            for i in range(B):
                if live[i] is not None or not pending:
                    continue
                j = self._admittable(pending, cur, reserved)
                if j is None:
                    continue
                r = pending.pop(j)
                plen = int(r.prompt.shape[-1])
                with TraceAnnotation("serve.admit"):
                    reserved[i] = pages_for_span(
                        cur - plen, cur + r.max_new_tokens, PS)
                    cache = self._palloc(cache, i, (cur - plen) // PS,
                                         (cur - 1) // PS)
                    tok0, cache = self._admit(r, cache, i, cur)
                    nxt = _put_token(nxt, jnp.asarray(i, jnp.int32), tok0)
                    s = live[i] = _Stream(r, i)
                    unread.append((tok0, (s,)))
                    start[i] = cur - plen
                    temps[i] = self._temp_of(r)
                    if s.spent:
                        cache = free(i, cache)
                admitted = True
            if all(s is None for s in live):
                break
            if cur >= self.max_len:  # defensive: budgets guarantee this
                break                # never trips (validated runways)
            if cur % PS == 0:
                # the shared cursor crosses into a fresh logical page: every
                # live slot gets one (reservation makes this infallible)
                act = [i for i, s in enumerate(live) if s is not None]
                with TraceAnnotation("serve.page_alloc"):
                    st, ok = alloc_step_pages_jit(
                        PageState(cache.kv.table, self._owner),
                        jnp.asarray(act, jnp.int32),
                        jnp.asarray(cur // PS, jnp.int32))
                    self._unchecked.append((ok, "the decode boundary"))
                    self._owner = st.owner
                    cache = self._with_table(cache, st.table)
            with TraceAnnotation("serve.dispatch"):
                if admitted:
                    start_d, temps_d = jnp.array(start), jnp.array(temps)
                self.steps += 1
                self.steps_ahead += bool(unread)
                nxt, cache = self._step(self.params, nxt, cache, start_d,
                                        temps_d, self._next_key())
            for s in live:
                if s is not None:
                    s.issued += 1
            # the previous step's tokens, while this one runs
            cache = read(unread, cache)
            unread = [(nxt, tuple(live))]
            cur += 1
        read(unread, cache)
        for s in live:  # only after the defensive break
            if s is not None and not s.closed:
                s.close()

    def _admittable(self, pending: List[Request], cur: int,
                    reserved: Dict[int, int]) -> Optional[int]:
        """Index of the first pending request that fits at cursor ``cur``:
        its prompt must fit below the cursor, its token budget inside the
        remaining cache depth, and its worst-case page span next to the
        live slots' reservations."""
        for j, r in enumerate(pending):
            plen = int(r.prompt.shape[-1])
            if plen > cur or cur + r.max_new_tokens > self.max_len:
                continue
            need = pages_for_span(cur - plen, cur + r.max_new_tokens,
                                  self._page_size)
            if sum(reserved.values()) + need > self._num_pages - 1:
                continue
            return j
        return None

    # -- page-pool bookkeeping ----------------------------------------------
    def _with_table(self, cache: DecodeCache, table) -> DecodeCache:
        kv = cache.kv
        return DecodeCache(
            PagedKVCache(kv.k, kv.v, table, kv.length, kv.page_size),
            cache.ssm, cache.length)

    def _palloc(self, cache: DecodeCache, slot: int, lo_page: int,
                hi_page: int) -> DecodeCache:
        """Map fresh pool pages at ``slot``'s logical pages [lo, hi]; the
        allocator's ``ok`` flag is read with the loop's next token read."""
        with TraceAnnotation("serve.page_alloc"):
            logical = jnp.arange(lo_page, hi_page + 1, dtype=jnp.int32)
            st, ok = alloc_slot_pages_jit(
                PageState(cache.kv.table, self._owner),
                jnp.asarray(slot, jnp.int32), logical)
            self._unchecked.append((ok, "prefill/admission"))
            self._owner = st.owner
            return self._with_table(cache, st.table)

    def _admit(self, r: Request, cache, slot: int, cur: int):
        """Prefill ``r`` alone and splice its KV rows into ``slot``'s
        freshly allocated pages at virtual positions ``[cur - plen, cur)``,
        and its Mamba-2 state into ``slot``'s state row; returns (first
        token, on the device as (1, 1), and cache). Under a mesh the
        prefill runs replicated over the data axes with the running batch's
        TP specs."""
        plen = int(r.prompt.shape[-1])
        p_adm = min(-(-plen // _ADMIT_ALIGN) * _ADMIT_ALIGN, cur)
        fn = self._admit_fn(p_adm)
        tokens = np.zeros((1, p_adm), np.int32)
        tokens[0, p_adm - plen:] = r.prompt
        nxt, cache = fn(self.params, jnp.asarray(tokens), cache,
                        jnp.asarray(slot, jnp.int32),
                        jnp.asarray(cur - p_adm, jnp.int32),
                        jnp.asarray([p_adm - plen], jnp.int32),
                        jnp.asarray([self._temp_of(r)], jnp.float32),
                        self._next_key())
        return nxt, cache

    def _admit_fn(self, p_adm: int):
        """Jitted single-request admission prefill, cached per padded
        prompt width (widths are rounded to ``_ADMIT_ALIGN`` to bound the
        number of traces)."""
        fn = self._admit_fns.get(p_adm)
        if fn is not None:
            return fn
        if self.comm_plan is not None:
            fn = self._build_admit_comm(p_adm)
        else:
            cfg = self.cfg
            model = Model(cfg)

            def admit(params, tokens, cache, slot, dest, start1, temp1, key):
                tmp = init_cache(cfg, 1, tokens.shape[1],
                                 dtype=self._cache_dtype)
                logits, _, tmp = model.forward(params, {"tokens": tokens},
                                               cache=tmp, start=start1)
                nxt = select_tokens(_last_logits(cfg, logits), temp1, key)
                kv = cache.kv
                if tmp.kv is not None:  # None: no attention layers
                    kv = paged_splice(kv, slot, dest,
                                      tmp.kv.k[:, 0], tmp.kv.v[:, 0])
                ssm = cache.ssm
                if ssm is not None:  # the slot's Mamba-2 state row
                    ssm = SSMState(
                        ssm.conv.at[:, slot].set(
                            tmp.ssm.conv[:, 0].astype(ssm.conv.dtype)),
                        ssm.ssd.at[:, slot].set(tmp.ssm.ssd[:, 0]))
                return nxt, DecodeCache(kv, ssm, cache.length)

            fn = jax.jit(admit, donate_argnums=(2,))
        self._admit_fns[p_adm] = fn
        return fn

    def _build_admit_comm(self, p_adm: int):
        """Admission prefill on the manual-TP (VCI stream) path: B=1
        replicates over the data axes, weights stay Megatron-sharded, the
        collectives ride lane 0's per-purpose streams, and the splice writes
        each rank's LOCAL KV heads into its local page pool shard."""
        cfg, mesh, plan = self.cfg, self.mesh, self.comm_plan
        assert mesh is not None
        tp = _mesh_tp(mesh)
        kvh = cfg.num_kv_heads * max(1, cfg.decode_kv_expand)
        kv_loc = kvh // tp if (tp > 1 and kvh % tp == 0) else kvh

        def admit(params, tokens, cache, slot, dest, start1, temp1, key):
            def inner(params, tokens, cache, slot, dest, start1, temp1, key):
                comm = plan.comm(0)
                model = Model(cfg, None, comm=comm)
                shape = (cfg.num_layers, 1, tokens.shape[1], kv_loc,
                         cfg.head_dim)
                dt = cache.kv.k.dtype
                tmp = DecodeCache(
                    KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                            jnp.zeros((), jnp.int32), False),
                    None, jnp.zeros((), jnp.int32))
                logits, _, tmp = model.forward(params, {"tokens": tokens},
                                               cache=tmp, start=start1)
                logits = comm.drain(logits)
                nxt = select_tokens(_last_logits(cfg, logits), temp1, key)
                kv = paged_splice(cache.kv, slot, dest,
                                  tmp.kv.k[:, 0], tmp.kv.v[:, 0])
                return nxt, DecodeCache(kv, None, cache.length)

            cspec = serve_cache_specs(cache, tp, 1)
            f = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(serve_param_specs(cfg, params, tp),
                          P(None, None), cspec, P(), P(), P(), P(), P()),
                out_specs=(P(None, None), cspec),
                check_vma=False, axis_names=set(mesh.axis_names))
            return f(params, tokens, cache, slot, dest, start1, temp1, key)

        return jax.jit(admit, donate_argnums=(2,))

    # -- grouped (equal prompt length) fallback ---------------------------
    def _run_grouped(self, reqs: List[Request]) -> None:
        cfg = self.cfg
        b = len(reqs)
        prompts = np.stack([r.prompt for r in reqs])
        cache = init_cache(cfg, b, self.max_len, dtype=self._cache_dtype)
        self._note_cache(cache)
        temps = np.asarray([self._temp_of(r) for r in reqs], np.float32)
        # comm-mode step functions take concrete (all-zero) start offsets;
        # the plain path keeps None (zamba2/audio reject per-row offsets).
        start = (None if self.comm_plan is None
                 else jnp.zeros((b,), jnp.int32))
        nxt, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(prompts)}, cache, start,
            jnp.asarray(temps), self._next_key())
        text = cfg.modality == "text"
        with TraceAnnotation("serve.sync"):
            gen = [np.asarray(nxt)]
        stopped = [False] * b

        def update_stops():
            if not text:
                return
            for i, r in enumerate(reqs):
                if r.stop_token is not None and \
                        int(gen[-1][i, 0]) == r.stop_token:
                    stopped[i] = True

        update_stops()
        while any(not stopped[i] and len(gen) < r.max_new_tokens
                  for i, r in enumerate(reqs)):
            with TraceAnnotation("serve.dispatch"):
                nxt, cache = self._step(self.params, nxt, cache, start,
                                        jnp.asarray(temps), self._next_key())
            with TraceAnnotation("serve.sync"):
                gen.append(np.asarray(nxt))
            update_stops()
        toks = np.concatenate(gen, axis=-1)  # (B,steps) or (B,K,steps)
        for i, r in enumerate(reqs):
            seq = toks[i][..., : r.max_new_tokens]
            if text and r.stop_token is not None:
                hits = np.nonzero(seq == r.stop_token)[0]
                if hits.size:
                    seq = seq[: int(hits[0])]
            r.generated = seq
