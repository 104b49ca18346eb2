"""Serving-engine tests."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from repro.configs import get_config
from repro.models.transformer import Model, init_params
from repro.serve.engine import (
    Request,
    ServeEngine,
    greedy_sample,
    make_prefill,
    make_serve_step,
    select_tokens,
    temperature_sample,
)
from repro.serve.paging import pages_for_span


def test_greedy_sample():
    logits = jnp.array([[[0.1, 2.0, -1.0]]])
    assert int(greedy_sample(logits)[0, 0]) == 1


def test_temperature_sample_valid_range():
    key = jax.random.PRNGKey(0)
    logits = jnp.zeros((4, 1, 16))
    toks = temperature_sample(key, logits, temperature=1.0)
    assert toks.shape == (4, 1)
    assert ((toks >= 0) & (toks < 16)).all()


def test_engine_generates():
    cfg = get_config("olmo-1b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64)
    reqs = [Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=5)
            for _ in range(3)]
    done = eng.generate(reqs)
    assert len(done) == 3
    for r in done:
        assert r.generated is not None
        assert r.generated.shape == (5,)
        assert ((r.generated >= 0) & (r.generated < cfg.vocab_size)).all()


def test_engine_greedy_is_deterministic():
    cfg = get_config("olmo-1b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32)
    def gen():
        rs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=6)
              for _ in range(2)]
        return [r.generated.copy() for r in eng.generate(rs)]
    a, b = gen(), gen()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_engine_audio_batch():
    cfg = get_config("musicgen-large-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32)
    K = cfg.num_codebooks
    reqs = [Request(prompt=np.zeros((K, 4), np.int32), max_new_tokens=3)]
    done = eng.generate(reqs)
    assert done[0].generated.shape == (K, 3)


def test_select_tokens_mixes_greedy_and_sampled_rows():
    key = jax.random.PRNGKey(0)
    logits = jnp.zeros((3, 1, 16)).at[:, 0, 5].set(4.0)
    temps = jnp.asarray([0.0, 1.0, 0.0])
    toks = select_tokens(logits, temps, key)
    assert toks.shape == (3, 1)
    assert int(toks[0, 0]) == 5 and int(toks[2, 0]) == 5  # greedy rows
    assert ((toks >= 0) & (toks < 16)).all()


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = get_config(arch)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def olmo_setup():
    return _setup("olmo-1b-smoke")


# Top-2 logit margin within which two programs that sum in different
# orders (a cache against a full forward) may pick either token.
NEAR_TIE = 1e-3
_REF_WIDTH = 64


@functools.lru_cache(maxsize=None)
def _last_logits_fn(cfg):
    model = Model(cfg)
    return jax.jit(lambda p, t, s: model.forward(
        p, {"tokens": t}, start=s)[0][0, -1])


def _assert_matches_forward(cfg, params, req: Request, what: str = ""):
    """Reference with no cache: greedy decoding by a full ``Model.forward``
    over the prompt plus the tokens picked so far (left-padded to one width,
    so one compile). ``req.generated`` must equal it, stop token included,
    up to the first position whose top-2 margin is within ``NEAR_TIE``;
    past such a position the two sequences follow different prefixes."""
    fwd = _last_logits_fn(cfg)
    got = [int(t) for t in req.generated]
    seq = [int(t) for t in req.prompt]
    for j in range(req.max_new_tokens):
        pad = _REF_WIDTH - len(seq)
        tokens = np.zeros((1, _REF_WIDTH), np.int32)
        tokens[0, pad:] = seq
        logits = np.asarray(fwd(params, jnp.asarray(tokens),
                                jnp.asarray([pad], jnp.int32)), np.float32)
        want = int(logits.argmax())
        if j < len(got):
            served = got[j]
        else:
            assert req.stop_token is not None, (
                f"{what}: {len(got)} of {req.max_new_tokens} tokens and no "
                f"stop token")
            served = req.stop_token
        if served != want:
            top2 = np.sort(logits)[-2:]
            assert top2[1] - top2[0] <= NEAR_TIE, (
                f"{what}: token {j} is {served}, the full forward picks "
                f"{want} at a top-2 margin of {top2[1] - top2[0]}")
            return
        if want == req.stop_token:
            assert len(got) == j, f"{what}: served past its stop token"
            return
        seq.append(want)
    assert len(got) == req.max_new_tokens, what


# the full and a right-sized page pool; mamba2 has no attention layers, so
# its pool holds 0 layers and only the per-slot state is live
POOLS = [("olmo-1b-smoke", {}),
         ("olmo-1b-smoke", {"page_size": 8, "num_pages": 13}),
         ("mamba2-780m-smoke", {})]


@pytest.mark.parametrize("arch,pool", POOLS)
def test_mixed_length_batch_matches_solo(arch, pool):
    """Regression for the min-length truncation bug: a batch of unequal
    prompt lengths must produce, for every request, the tokens it would
    produce alone (left-padding + masked prefill)."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                        dtype=np.int32), max_new_tokens=6)
            for plen in (3, 11, 7)]
    eng = ServeEngine(cfg, params, batch_size=3, max_len=64, **pool)
    eng.generate(reqs)
    for r in reqs:
        _assert_matches_forward(
            cfg, params, r,
            f"{arch} prompt len {r.prompt.shape[-1]} (batched)")


def test_cache_overflow_rejected(olmo_setup):
    """plen + max_new_tokens > max_len must raise at generate() time, not
    silently wrap the cache write cursor."""
    cfg, params = olmo_setup
    eng = ServeEngine(cfg, params, batch_size=2, max_len=16)
    ok = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=8)
    bad = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=9)
    with pytest.raises(ValueError, match="exceeds the cache depth"):
        eng.generate([ok, bad])
    assert bad.generated is None  # rejected before any decoding
    eng.generate([ok])            # the boundary case fits exactly
    assert ok.generated.shape == (8,)


def test_per_request_max_new_tokens(olmo_setup):
    """Each request decodes ITS budget — not max() over the batch."""
    cfg, params = olmo_setup
    reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=n)
            for n in (2, 7, 4)]
    eng = ServeEngine(cfg, params, batch_size=3, max_len=32)
    eng.generate(reqs)
    assert [r.generated.shape[-1] for r in reqs] == [2, 7, 4]
    for r in reqs:
        _assert_matches_forward(cfg, params, r)


def test_stop_token_early_exit(olmo_setup):
    """A request finishes at its stop token; tokens before it match the
    un-stopped run."""
    cfg, params = olmo_setup
    base = Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=8)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32)
    eng.generate([base])
    assert base.generated.shape == (8,)
    # first position whose token hasn't occurred before = unambiguous stop
    j = next(j for j in range(1, 8)
             if base.generated[j] not in base.generated[:j])
    stop = int(base.generated[j])
    stopped = Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=8,
                      stop_token=stop)
    other = Request(prompt=np.arange(9, dtype=np.int32), max_new_tokens=8)
    eng.generate([stopped, other])
    np.testing.assert_array_equal(stopped.generated, base.generated[:j])
    assert other.generated.shape == (8,)


@pytest.mark.parametrize("arch,pool", POOLS)
def test_continuous_batching_recycles_slots(arch, pool):
    """More requests than slots with unequal lengths/budgets: every request
    completes with its solo tokens (early admission into freed slots must
    not leak the previous occupant's cache or state), and every page is
    back in the pool when the run drains (per-slot compaction for free)."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                        dtype=np.int32), max_new_tokens=n)
            for plen, n in ((5, 3), (9, 6), (4, 8), (7, 2), (6, 5))]
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64, **pool)
    eng.generate(reqs)
    for i, r in enumerate(reqs):
        _assert_matches_forward(cfg, params, r,
                                f"{arch} request {i} (slot recycling)")
    owner = np.asarray(eng._owner)
    assert owner[0] == -2 and (owner[1:] == -1).all(), \
        f"pages leaked after drain: {owner}"


def test_decode_matches_forward_argmax(olmo_setup):
    """Conformance: N greedy decode steps equal the argmax tail of one full
    forward over prompt + generated tokens (teacher-forcing check)."""
    cfg, params = olmo_setup
    req = Request(prompt=np.arange(5, dtype=np.int32), max_new_tokens=6)
    eng = ServeEngine(cfg, params, batch_size=1, max_len=32)
    eng.generate([req])
    full = np.concatenate([req.prompt, req.generated[:-1]])
    model = Model(cfg)
    logits, _, _ = jax.jit(model.forward)(params,
                                          {"tokens": jnp.asarray(full)[None]})
    want = np.asarray(jnp.argmax(logits[0, req.prompt.shape[-1] - 1:], -1))
    np.testing.assert_array_equal(req.generated, want)


def test_ring_cache_mixed_lengths_grouped():
    """Sliding-window (ring cache) archs can't left-pad; the engine must
    fall back to equal-length groups and still serve mixed lengths."""
    cfg = get_config("mixtral-8x22b-smoke")   # sliding_window=64
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=96)  # 96 > window
    assert cfg.sliding_window < 96 and not eng._padded_ok
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                        dtype=np.int32), max_new_tokens=3)
            for plen in (4, 8, 4)]
    eng.generate(reqs)
    solo = ServeEngine(cfg, params, batch_size=1, max_len=96)
    for r in reqs:
        assert r.generated.shape == (3,)
        ref = Request(prompt=r.prompt.copy(), max_new_tokens=3)
        solo.generate([ref])
        np.testing.assert_array_equal(r.generated, ref.generated)


def test_temperature_zero_matches_greedy(olmo_setup):
    cfg, params = olmo_setup
    tzero = Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=4,
                    temperature=0.0)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, temperature=0.7)
    # engine default 0.7 applies only where the request doesn't override
    eng.generate([tzero])
    _assert_matches_forward(cfg, params, tzero)


def test_temperature_sampling_decodes_valid_tokens(olmo_setup):
    cfg, params = olmo_setup
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, seed=1)
    reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=5,
                    temperature=1.0) for _ in range(2)]
    eng.generate(reqs)
    for r in reqs:
        assert r.generated.shape == (5,)
        assert ((r.generated >= 0) & (r.generated < cfg.vocab_size)).all()


def test_serve_step_matches_engine_stepping():
    cfg = get_config("olmo-1b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    from repro.models.transformer import init_cache
    cache = init_cache(cfg, 1, 16, dtype=jnp.float32)
    prompt = jnp.arange(4, dtype=jnp.int32)[None]
    nxt, cache = jax.jit(make_prefill(cfg))(params, {"tokens": prompt}, cache)
    step = jax.jit(make_serve_step(cfg))
    seq = [int(nxt[0, 0])]
    for _ in range(4):
        nxt, cache = step(params, nxt, cache)
        seq.append(int(nxt[0, 0]))
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16,
                      cache_dtype=jnp.float32)
    [req] = eng.generate([Request(prompt=np.arange(4, dtype=np.int32),
                                  max_new_tokens=5)])
    np.testing.assert_array_equal(np.array(seq), req.generated)


# ---------------------------------------------------------------------------
# the page pool
# ---------------------------------------------------------------------------

def test_contiguous_continuous_cache_rejected(olmo_setup):
    """``paged=False`` names the removed contiguous continuous cache: the
    constructor refuses it rather than silently serving paged."""
    cfg, params = olmo_setup
    with pytest.raises(ValueError, match="contiguous continuous cache"):
        ServeEngine(cfg, params, batch_size=2, max_len=32, paged=False)


def test_paged_lower_resident_bytes_than_contiguous(olmo_setup):
    """At equal traffic a right-sized page pool keeps fewer resident cache
    bytes than the default full pool (``batch * max_len`` positions, as a
    contiguous cache would) — the paging payoff."""
    cfg, params = olmo_setup
    def mk():
        rng = np.random.default_rng(5)
        return [Request(prompt=rng.integers(0, cfg.vocab_size, (p,),
                                            dtype=np.int32),
                        max_new_tokens=4) for p in (6, 9, 5, 8)]
    eng_full = ServeEngine(cfg, params, batch_size=4, max_len=128,
                           page_size=8)
    eng_p = ServeEngine(cfg, params, batch_size=4, max_len=128,
                        page_size=8, num_pages=13)
    a, b = mk(), mk()
    eng_full.generate(a)
    eng_p.generate(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.generated, y.generated)
    assert 0 < eng_p.cache_bytes_resident < eng_full.cache_bytes_resident


def test_paged_stop_token_and_budgets(olmo_setup):
    """Per-request budgets and stop tokens behave identically paged — the
    stop-token finish (record() without appending) must also reclaim the
    slot's pages mid-page."""
    cfg, params = olmo_setup
    reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=n)
            for n in (2, 7, 4)]
    eng = ServeEngine(cfg, params, batch_size=3, max_len=32, page_size=4,
                      num_pages=13)
    eng.generate(reqs)
    assert [r.generated.shape[-1] for r in reqs] == [2, 7, 4]
    for r in reqs:
        _assert_matches_forward(cfg, params, r)

    # a stop token that actually fires: truncates at the un-stopped run's
    # first repeat-free position, and the drained pool holds no pages
    base = reqs[1]                        # 7 greedy tokens
    j = next(j for j in range(1, 7)
             if base.generated[j] not in base.generated[:j])
    stopped = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=7,
                      stop_token=int(base.generated[j]))
    other = Request(prompt=np.arange(9, dtype=np.int32), max_new_tokens=7)
    eng.generate([stopped, other])
    np.testing.assert_array_equal(stopped.generated, base.generated[:j])
    assert other.generated.shape == (7,)
    owner = np.asarray(eng._owner)
    assert owner[0] == -2 and (owner[1:] == -1).all(), \
        f"stop-token finish leaked pages: {owner}"


def test_paged_pool_too_small_rejected(olmo_setup):
    """A request whose worst-case page span exceeds the pool must be
    rejected at generate() time, not starve the allocator mid-decode."""
    cfg, params = olmo_setup
    eng = ServeEngine(cfg, params, batch_size=1, max_len=64, page_size=8,
                      num_pages=4)  # 3 allocatable pages
    ok = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=16)
    bad = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=17)
    with pytest.raises(ValueError, match="grow the pool"):
        eng.generate([bad])
    eng.generate([ok])
    assert ok.generated.shape == (16,)


def test_paged_falls_back_for_ring_cache():
    """Sliding-window (ring) archs have no paged layout: the engine keeps
    the grouped contiguous fallback and still serves correctly."""
    cfg = get_config("mixtral-8x22b-smoke")   # sliding_window=64
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=96)
    assert cfg.sliding_window < 96 and not eng._paged
    reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=3)
            for _ in range(2)]
    eng.generate(reqs)
    for r in reqs:
        assert r.generated.shape == (3,)


@pytest.mark.parametrize("arch", ["olmo-1b-smoke",
                                  "granite-4.0-h-micro-smoke"])
def test_paged_kernel_engine_matches_gather_path(arch, monkeypatch):
    """The paged engine with its decode attention on the TPU kernel (run
    in interpret mode) serves the tokens of the gather path, through a
    batch prefill and an admission into a freed slot, up to a request's
    first token whose full-forward top-2 margin is within ``NEAR_TIE``:
    both sides compute in float32 and differ only in the order of their
    sums."""
    import functools

    from repro.kernels import paged_kv

    cfg = get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(4)
    shapes = ((5, 3), (11, 9), (8, 6))      # the third is admitted

    def serve():
        reqs = [Request(prompt=rng_prompts[i], max_new_tokens=n)
                for i, (_, n) in enumerate(shapes)]
        eng = ServeEngine(cfg, params, batch_size=2, max_len=48, page_size=8)
        eng.generate(reqs)
        return [r.generated for r in reqs]

    rng_prompts = [rng.integers(0, cfg.vocab_size, (p,), dtype=np.int32)
                   for p, _ in shapes]
    admitted = []
    admit = ServeEngine._admit
    monkeypatch.setattr(ServeEngine, "_admit", lambda self, r, *a: (
        admitted.append(r), admit(self, r, *a))[1])
    want = serve()
    assert admitted, "no request was admitted into a freed slot"
    calls = []
    kernel = paged_kv.paged_decode_attention_pallas

    def counted(*a, **kw):
        calls.append(1)
        return kernel(*a, **kw)

    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_kv, "paged_decode_attention_pallas",
                        functools.partial(counted, interpret=True))
    got = serve()
    assert calls, "the decode step never reached the kernel"
    model = Model(cfg)
    for prompt, a, b in zip(rng_prompts, want, got):
        assert a.shape == b.shape
        diff = np.nonzero(a != b)[0]
        if diff.size == 0:
            continue
        d = int(diff[0])
        seq = np.concatenate([prompt, a[:d]])[None]
        logits = np.asarray(model.forward(params, {"tokens": seq})[0][0, -1],
                            np.float32)
        top2 = np.sort(logits)[-2:]
        assert top2[1] - top2[0] <= NEAR_TIE, (
            f"{arch}: token {d} differs at a top-2 margin of "
            f"{top2[1] - top2[0]}")


# ---------------------------------------------------------------------------
# dispatch ahead: each step's tokens are read while the next step runs
# ---------------------------------------------------------------------------

def _record_admissions(monkeypatch):
    """Wrap ``ServeEngine._admit``; returns the list it fills with
    (request, slot, cursor) of every admission."""
    admitted = []
    admit = ServeEngine._admit
    monkeypatch.setattr(
        ServeEngine, "_admit", lambda self, r, cache, slot, cur: (
            admitted.append((r, slot, cur)),
            admit(self, r, cache, slot, cur))[1])
    return admitted


def _budget_schedule(sizes, batch: int, pad: int):
    """(admission cursors, cursor of the last finish) when every request
    runs to its budget: a request admitted at cursor ``a`` with a budget of
    ``n`` leaves its slot at ``a + n - 1``, and the next pending request
    takes the slot at that cursor (the lowest slot first on a tie)."""
    free_at = [pad + n - 1 for _, n in sizes[:batch]]
    cursors = []
    for _, n in sizes[batch:]:
        i = free_at.index(min(free_at))
        cursors.append(free_at[i])
        free_at[i] += n - 1
    return cursors, max(free_at)


@pytest.mark.parametrize("arch,pool", POOLS)
def test_dispatch_ahead_keeps_the_budget_schedule(arch, pool, monkeypatch):
    """Budget-only traffic, more requests than slots, over several page
    boundaries: every decode step (the first too, which takes the batch
    prefill's tokens on the device) is dispatched before the previous
    step's tokens are read; admissions land at the cursors the budgets
    alone give; every request gets the tokens of the cache-free forward."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(13)
    sizes = ((5, 9), (9, 14), (4, 11), (7, 6), (6, 12), (3, 10), (8, 13))
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (p,),
                                        dtype=np.int32), max_new_tokens=n)
            for p, n in sizes]
    admitted = _record_admissions(monkeypatch)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      **{"page_size": 8, **pool})
    eng.generate(reqs)
    pad = max(p for p, _ in sizes[:2])
    cursors, last = _budget_schedule(sizes, 2, pad)
    assert [r for r, _, _ in admitted] == reqs[2:]
    assert [c for _, _, c in admitted] == cursors
    assert last - pad > 3 * 8, "the run crosses several page boundaries"
    assert eng.steps == last - pad
    assert eng.steps_ahead == eng.steps
    for i, r in enumerate(reqs):
        _assert_matches_forward(cfg, params, r, f"{arch} request {i}")
    owner = np.asarray(eng._owner)
    assert owner[0] == -2 and (owner[1:] == -1).all(), owner


def _pages_within_reservations(eng, monkeypatch):
    """After every page allocation, each slot owns at most the pages it
    reserved, and no page belongs to a slot without a reservation."""
    def checked(fn):
        def call(state, *a):
            st, ok = fn(state, *a)
            owner = np.asarray(st.owner)
            assert set(owner[owner >= 0].tolist()) <= set(eng._reserved)
            for slot, n in eng._reserved.items():
                assert (owner == slot).sum() <= n, (slot, n, owner)
            return st, ok
        return call

    for name in ("alloc_slot_pages_jit", "alloc_step_pages_jit"):
        monkeypatch.setattr(engine_mod, name,
                            checked(getattr(engine_mod, name)))


@pytest.mark.parametrize("case", ["mid_page", "page_boundary",
                                  "last_token"])
def test_late_stop_token(olmo_setup, monkeypatch, case):
    """A stop token is read one step late. Before its last budget token
    the slot runs one extra step (``page_boundary``: that step crosses
    into a fresh page), inside its reservation, and a pending request takes
    the slot at the next cursor; on its last budget token the host already
    knows the finish and admits at once. Every request gets the tokens of
    the cache-free forward, and the pool drains."""
    cfg, params = olmo_setup
    ps, pad, n = 4, 9, 16
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, (6,), dtype=np.int32)
    base = Request(prompt=prompt, max_new_tokens=n)
    ServeEngine(cfg, params, batch_size=1, max_len=48,
                page_size=ps).generate([base])
    g = base.generated.tolist()
    # a stop on token k fires at k only where the token is new there;
    # token k is written at position pad + k by the extra step
    new = [k for k in range(1, n - 1) if g[k] not in g[:k]]
    if case == "last_token":
        k = new[0]
        n = k + 1
    else:
        k = next(k for k in new if ((pad + k) % ps == 0)
                 == (case == "page_boundary"))
    stopped = Request(prompt=prompt, max_new_tokens=n, stop_token=g[k])
    other = Request(prompt=rng.integers(0, cfg.vocab_size, (pad,),
                                        dtype=np.int32), max_new_tokens=20)
    waiting = Request(prompt=rng.integers(0, cfg.vocab_size, (5,),
                                          dtype=np.int32), max_new_tokens=6)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=48, page_size=ps)
    admitted = _record_admissions(monkeypatch)
    _pages_within_reservations(eng, monkeypatch)
    eng.generate([stopped, other, waiting])
    np.testing.assert_array_equal(stopped.generated, g[:k])
    late = 0 if case == "last_token" else 1
    assert [(r, slot, cur) for r, slot, cur in admitted] == [
        (waiting, 0, pad + k + late)]
    for r in (stopped, other, waiting):
        _assert_matches_forward(cfg, params, r, case)
    owner = np.asarray(eng._owner)
    assert owner[0] == -2 and (owner[1:] == -1).all(), \
        f"late stop leaked pages: {owner}"


def test_broken_reservation_raises_before_generate_returns(olmo_setup,
                                                           monkeypatch):
    """The allocator's ``ok`` flags are read after the next dispatch, not
    at once; a reservation that under-counts by a page still makes
    ``generate`` raise before it returns."""
    cfg, params = olmo_setup
    monkeypatch.setattr(engine_mod, "pages_for_span",
                        lambda s, e, ps: max(0, pages_for_span(s, e, ps) - 1))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=32, page_size=4,
                      num_pages=7)
    reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=12)
            for _ in range(2)]
    with pytest.raises(RuntimeError, match="reservation accounting broken"):
        eng.generate(reqs)
