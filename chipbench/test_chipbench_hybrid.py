"""granite-4.0-h-micro against its plain float32 reference at a smoke size
that keeps one whole period of its layer pattern (``MMMMMAMMMM``, d 128,
float32 weights and activations), and the layer-typed counts of
``ops_hybrid`` against ``ops``.

Every comparison is of logits and holds to ``TOL`` of the reference's
largest logit: both sides compute in float32, and they differ only in the
order of their sums (the chunked SSD against the quadratic form, paged
attention against whole attention, a padded batch against one sequence).
The float8 control misses by more than 1e-2 of that scale; a slot whose
Mamba-2 state row is not written on admission, or a pad token that steps a
state, misses by far more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, ops, ops_hybrid
from chipbench.reference import hybrid

TOL = 1e-5


@pytest.fixture(scope="module")
def granite():
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config("granite-4.0-h-micro-smoke"),
                              d_model=128)
    d = dataclasses.asdict(cfg)
    params = jax.jit(lambda k: hybrid.init_params(d, k))(jax.random.key(3))
    return cfg, d, params


def _close(got, want):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= TOL * scale


def _ref(params, d, seq):
    return hybrid.forward(params, jnp.asarray(seq, jnp.int32)[None], d)[0]


def test_params_are_the_programs_layout(granite):
    from repro.models.transformer import init_params

    cfg, _, params = granite
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_forward_matches_reference(granite):
    from repro.models.transformer import Model

    cfg, d, params = granite
    toks = jax.random.randint(jax.random.key(4), (2, 40), 0, cfg.vocab_size)
    prog = Model(cfg).forward(params, {"tokens": toks})[0]
    want = hybrid.forward(params, toks, d)
    _close(prog, want)
    ctrl = hybrid.forward(params, toks, d, control=True)
    assert float(jnp.abs(ctrl - want).max()) >= 1e-2 * float(
        jnp.abs(want).max())


def test_paged_prefill_then_decode_matches_reference(granite):
    from repro.models.transformer import Model, init_paged_cache

    cfg, d, params = granite
    model = Model(cfg)
    toks = jax.random.randint(jax.random.key(5), (2, 40), 0, cfg.vocab_size)
    cache = init_paged_cache(cfg, 2, 48, page_size=8, num_pages=13,
                             dtype=jnp.float32)
    table = jnp.arange(1, 13, dtype=jnp.int32).reshape(2, 6)
    kv = cache.kv
    cache = cache._replace(kv=type(kv)(kv.k, kv.v, table, kv.length,
                                       kv.page_size))
    plen = 21
    logits, _, cache = model.forward(params, {"tokens": toks[:, :plen]},
                                     cache=cache)
    got = [logits[:, -1:]]
    step = jax.jit(model.decode_step)
    for t in range(plen, 40):
        lg, cache = step(params, toks[:, t:t + 1], cache)
        got.append(lg)
    _close(jnp.concatenate(got, 1), hybrid.forward(params, toks, d)[:, plen - 1:])


def test_continuous_batch_logits_match_each_request_alone(granite):
    """The engine's own prefill, admission and step programs' logits, with
    left-padded prompts of mixed lengths and a request admitted mid-stream
    into a freed slot, against each request's reference forward alone."""
    from repro.models.transformer import Model, init_paged_cache
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.paging import PageState, free_slot_pages_jit, \
        page_state_init

    cfg, d, params = granite
    model = Model(cfg)
    b, max_len, ps = 3, 64, 8
    eng = ServeEngine(cfg, params, batch_size=b, max_len=max_len, paged=True,
                      page_size=ps)
    rng = np.random.default_rng(0)
    v = cfg.vocab_size
    prompts = [rng.integers(0, v, n).tolist() for n in (5, 13, 9)]
    pad = max(map(len, prompts))
    start = np.asarray([pad - len(p) for p in prompts], np.int32)
    toks = np.zeros((b, pad), np.int32)
    for i, p in enumerate(prompts):
        toks[i, start[i]:] = p
    cache = init_paged_cache(cfg, b, max_len, page_size=ps,
                             num_pages=eng._num_pages, dtype=jnp.float32)
    eng._owner = page_state_init(eng._num_pages, b, eng._max_pages).owner
    last = (max_len - 1) // ps
    for i in range(b):
        cache = eng._palloc(cache, i, int(start[i]) // ps, last)
    logits, _, cache = model.forward(params, {"tokens": jnp.asarray(toks)},
                                     cache=cache, start=jnp.asarray(start))
    seqs = [list(p) for p in prompts]
    got = [[logits[i, -1]] for i in range(b)]
    done = []  # (sequence, logits) of finished requests
    step = jax.jit(model.decode_step)
    for k in range(12):
        if k == 4:  # slot 1 finishes; a 7-token prompt takes its place
            done.append((seqs[1], got[1]))
            cur = int(cache.kv.length)
            st = free_slot_pages_jit(PageState(cache.kv.table, eng._owner),
                                     jnp.asarray(1, jnp.int32))
            eng._owner = st.owner
            cache = eng._with_table(cache, st.table)
            new = rng.integers(0, v, 7).tolist()
            cache = eng._palloc(cache, 1, (cur - len(new)) // ps, last)
            _, cache = eng._admit(Request(prompt=np.asarray(new, np.int32),
                                          max_new_tokens=8), cache, 1, cur)
            start[1] = cur - len(new)
            seqs[1], got[1] = new, []
        fed = rng.integers(0, v, b)
        lg, cache = step(params, jnp.asarray(fed[:, None], jnp.int32), cache,
                         start=jnp.asarray(start))
        for i in range(b):
            seqs[i].append(int(fed[i]))
            got[i].append(lg[i, 0])
    for seq, lgs in done + list(zip(seqs, got)):
        want = _ref(params, d, seq)
        _close(jnp.stack(lgs), want[len(seq) - len(lgs):])


def test_engine_serves_granite_continuously(granite, monkeypatch):
    """A granite-shaped config takes the continuous paged path (never the
    grouped fallback), admits mid-stream, and serves each request the
    tokens it gets alone."""
    from repro.serve.engine import Request, ServeEngine

    cfg, _, params = granite
    monkeypatch.setattr(ServeEngine, "_run_grouped", lambda *a: 1 / 0)
    admitted = []
    admit = ServeEngine._admit
    monkeypatch.setattr(ServeEngine, "_admit", lambda self, r, *a: (
        admitted.append(r), admit(self, r, *a))[1])
    rng = np.random.default_rng(1)

    def reqs():
        return [Request(prompt=rng.integers(0, cfg.vocab_size, n,
                                            dtype=np.int32),
                        max_new_tokens=m)
                for n, m in ((5, 3), (11, 6), (8, 4), (6, 5))]

    batch = reqs()
    eng = ServeEngine(cfg, params, batch_size=2, max_len=48, paged=True,
                      page_size=8)
    assert eng._padded_ok and eng._paged
    eng.generate(batch)
    assert admitted, "no request was admitted mid-stream"
    for r in batch:
        alone = Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        ServeEngine(cfg, params, batch_size=1, max_len=48, paged=True,
                    page_size=8).generate([alone])
        np.testing.assert_array_equal(r.generated, alone.generated)


def test_published_cache_layout():
    """At the published size: K/V pages for the 4 attention layers only,
    and per-slot Mamba-2 state for the 36 others in the same cache."""
    from repro.configs import get_config
    from repro.models.transformer import init_paged_cache

    cfg = get_config("granite-4.0-h-micro")
    c = jax.eval_shape(lambda: init_paged_cache(
        cfg, 16, 2048, page_size=16, num_pages=2049, dtype=jnp.float32))
    # 8 KV heads of 64 a token, kept as one row of 512 (heads under 128)
    assert c.kv.k.shape == (4, 2049, 16, 8 * 64)
    assert c.ssm.ssd.shape == (36, 16, 64, 128, 64)
    assert c.ssm.ssd.dtype == jnp.float32
    assert c.ssm.conv.shape == (36, 16, 3, 4096 + 256)
    assert c.ssm.conv.dtype == jnp.float32


GRANITE = harness.load_json(harness.HERE, "configs", "granite-4.0-h-micro.json")
OLMO = harness.load_json(harness.HERE, "configs", "olmo-1b.json")
MAMBA = harness.load_json(harness.HERE, "configs", "mamba2-780m.json")


def test_ops_hybrid_gives_ops_numbers_on_olmo():
    reqs = [(20, 3), (1536, 32), (7, 1)]
    assert ops_hybrid.matmul_params(OLMO) == ops.matmul_params(OLMO)
    assert ops_hybrid.weight_bytes(OLMO) == ops.weight_bytes(OLMO)
    assert ops_hybrid.attention_flops(OLMO, 100) == \
        ops.attention_flops(OLMO, 100)
    assert ops_hybrid.serve_flops(OLMO, reqs) == ops.serve_flops(OLMO, reqs)
    assert ops_hybrid.kv_token_bytes(OLMO) == ops.kv_token_bytes(OLMO)
    assert ops_hybrid.decode_least_bytes(OLMO, reqs) == \
        ops.decode_least_bytes(OLMO, reqs)
    assert ops_hybrid.decode_state_bytes(OLMO, "float32", reqs) == 0
    # an all-Mamba-2 config: ops.py's products and SSD work
    assert ops_hybrid.matmul_params(MAMBA) == ops.matmul_params(MAMBA)
    assert ops_hybrid.ssd_flops(MAMBA) == ops.ssd_flops(MAMBA)


def test_ops_hybrid_granite_counts():
    from repro.configs import get_config

    # 36 Mamba-2 layers: in_proj 2048 x (2 * 4096 + 256 + 64), out_proj
    # 4096 x 2048, conv 4 x 4352; 4 attention layers: 2048 x (2048 + 2 *
    # 512) + 2048 x 2048; 40 MLPs 3 x 2048 x 8192; the tied 100,352-row head
    mamba = 2048 * 8512 + 4096 * 2048 + 4 * 4352
    attn = 2048 * 3072 + 2048 * 2048
    n = 36 * mamba + 4 * attn + 40 * 3 * 2048 * 8192 + 100_352 * 2048
    assert ops_hybrid.matmul_params(GRANITE) == n == 3_190_919_168
    # the published 3.19B, with the conv bias, A and D
    assert get_config("granite-4.0-h-micro").param_count() == \
        n + 36 * (4352 + 2 * 64)
    # K and V of 4 layers, 8 heads of 64, bf16
    assert ops_hybrid.kv_token_bytes(GRANITE) == 2 * 4 * 8 * 64 * 2
    # per slot: SSD state 64 x 128 x 64 in f32, conv window 3 x 4352
    assert ops_hybrid.state_bytes(GRANITE, "float32") == \
        36 * (64 * 128 * 64 * 4 + 3 * 4352 * 4)
    # a request of 4 prompt tokens and 3 served feeds back 2: each reads
    # and writes the state; 6 tokens pass the products and the recurrence
    # (4 N P a head on 36 layers), and attend to contexts 1..6 on 4 layers
    assert ops_hybrid.decode_state_bytes(GRANITE, "float32", [(4, 3)]) == \
        2 * 2 * ops_hybrid.state_bytes(GRANITE, "float32")
    assert ops_hybrid.serve_flops(GRANITE, [(4, 3)]) == \
        6 * (2 * n + 36 * 64 * 4 * 128 * 64) + 4 * 4 * 32 * 64 * 21


def test_hand_counted_hybrid_metrics():
    from chipbench.test_chipbench_trace import ctx_of, serve_trace

    ctx = ctx_of(serve_trace(), requests=[(20, 2)], cache_dtype="float32",
                 engine={"batch_size": 1, "max_len": 64, "page_size": 16})
    ctx["config"] = GRANITE
    read = lambda name: harness.load_reader(name)(ctx)
    n = 3_190_919_168
    state = 36 * (64 * 128 * 64 * 4 + 3 * 4352 * 4)
    # one 800 ns step: the bf16 weights once, 21 tokens of bf16 K and V on
    # 4 layers, and the one fed-back token's state read and written
    least = 2 * n + 21 * 2 * 4 * 8 * 64 * 2 + 2 * state
    assert read("decode_hbm_share_hybrid.serve") == pytest.approx(
        100 * least / 800e-9 / 1e15)
    # 21 tokens through the products and the recurrence, contexts 1..21 on
    # the 4 attention layers, over the 2000 ns window
    flops = 21 * (2 * n + 36 * 64 * 4 * 128 * 64) + 4 * 4 * 32 * 64 * 231
    assert read("mfu_hybrid.serve") == pytest.approx(
        100 * flops / 2000e-9 / 1e15)
    ctx["window"] = (1400, 2000)
    ctx["inputs"]["requests"] = []
    assert read("decode_hbm_share_hybrid.serve") is None
    assert read("mfu_hybrid.serve") is None
