"""Pallas kernel sweeps vs pure-jnp oracles (interpret=True on CPU).

Every kernel in repro.kernels is swept over shapes and dtypes and asserted
allclose against its ref.py oracle, per the assignment.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import flash_attention, row_gather, ssd_chunked
from repro.kernels.moe_gather import row_gather_ref
from repro.models import ssm as ssm_mod

jax.config.update("jax_enable_x64", False)


def _qkv(key, b, h, kv, sq, sk, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, kv, sk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, kv, sk, hd), jnp.float32).astype(dtype)
    return q, k, v


_TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("sq,sk", [(128, 128), (256, 128), (128, 384),
                                       (96, 160), (64, 64)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_shapes_causal(self, sq, sk, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0), 2, 4, 4, sq, sk, 64, jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        expect = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, expect, **_TOL[jnp.float32])

    @pytest.mark.parametrize("h,kv", [(8, 8), (8, 2), (8, 1)])
    def test_gqa_mqa(self, h, kv):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, h, kv, 128, 128, 64,
                       jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(out, expect, **_TOL[jnp.float32])

    @pytest.mark.parametrize("window", [32, 64, 128])
    def test_sliding_window(self, window):
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 2, 256, 256, 32,
                       jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64, interpret=True)
        expect = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(out, expect, **_TOL[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q, k, v = _qkv(jax.random.PRNGKey(3), 2, 4, 2, 128, 128, 64, dtype)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert out.dtype == dtype
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   expect.astype(jnp.float32), **_TOL[dtype])

    def test_ragged_seq_padding(self):
        """seq not a multiple of the block: padded KV rows must not leak."""
        q, k, v = _qkv(jax.random.PRNGKey(4), 1, 2, 2, 100, 100, 32,
                       jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(out, expect, **_TOL[jnp.float32])

    def test_head_dim_256(self):
        """gemma-2b uses head_dim=256."""
        q, k, v = _qkv(jax.random.PRNGKey(5), 1, 4, 1, 128, 128, 256,
                       jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(out, expect, **_TOL[jnp.float32])


class TestSSDKernel:
    @pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64), (64, 64)])
    @pytest.mark.parametrize("g", [1, 2])
    def test_matches_model_reference(self, s, chunk, g):
        b, h, p, n = 2, 4, 32, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
        y_k, st_k = ssd_chunked(x, dt, A, B, C, chunk=chunk, interpret=True)
        y_r, st_r = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk=chunk)
        np.testing.assert_allclose(y_k, y_r, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(st_k, st_r, rtol=2e-4, atol=2e-4)

    def test_chunk_oracle(self):
        """The single-chunk kernel vs the per-chunk pure oracle."""
        c, p, n = 32, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        x = jax.random.normal(ks[0], (c, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (c,)))
        A = -jnp.exp(jax.random.normal(ks[2], ()) * 0.3)
        cum = jnp.cumsum(dt * A)
        B = jax.random.normal(ks[3], (c, n)) * 0.3
        C = jax.random.normal(ks[4], (c, n)) * 0.3
        y, st = ref.ssd_chunk_ref(x, dt, cum, B, C)
        assert y.shape == (c, p) and st.shape == (n, p)
        # oracle self-consistency vs the naive recurrence
        s_state = jnp.zeros((n, p))
        ys = []
        prev_cum = 0.0
        for t in range(c):
            decay = jnp.exp(cum[t] - prev_cum)
            s_state = decay * s_state + dt[t] * B[t][:, None] * x[t][None, :]
            ys.append(C[t] @ s_state)
            prev_cum = cum[t]
        np.testing.assert_allclose(y, jnp.stack(ys), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(st, s_state, rtol=1e-4, atol=1e-4)

    def test_decode_step_consistent_with_chunked(self):
        """Sequential O(1) decode steps == the blocked scan."""
        b, s, h, p, n, g = 1, 16, 2, 8, 4, 1
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
        y_blk, st_blk = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk=8)
        st = jnp.zeros((b, h, n, p))
        ys = []
        for t in range(s):
            y_t, st = ssm_mod.ssd_decode_step(
                st, x[:, t], dt[:, t], A, B[:, t], C[:, t])
            ys.append(y_t)
        y_seq = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(y_blk, y_seq, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(st_blk, st, rtol=1e-4, atol=1e-4)

    def test_initial_state_propagates(self):
        """ssd_chunked(init) == running the prefix then the suffix."""
        b, s, h, p, n, g = 1, 32, 2, 8, 4, 1
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
        y_full, st_full = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk=8)
        _, st_half = ssm_mod.ssd_chunked(
            x[:, :16], dt[:, :16], A, B[:, :16], C[:, :16], chunk=8)
        y2, st2 = ssm_mod.ssd_chunked(
            x[:, 16:], dt[:, 16:], A, B[:, 16:], C[:, 16:], chunk=8,
            initial_state=st_half)
        np.testing.assert_allclose(y2, y_full[:, 16:], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(st2, st_full, rtol=1e-4, atol=1e-4)

    def test_ragged_seq_pad(self):
        """seq not a multiple of chunk pads with dt=0 (exact)."""
        b, s, h, p, n, g = 1, 20, 2, 8, 4, 1
        ks = jax.random.split(jax.random.PRNGKey(4), 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
        y8, st8 = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk=8)
        y20, st20 = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk=20)
        np.testing.assert_allclose(y8, y20, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(st8, st20, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_strong_decay_at_chunk_256_stays_finite(self, impl):
        """At chunk 256 with strong decay, exp(cum_i - cum_j) above the
        diagonal overflows; it is masked before the exp, so the forward is
        finite and the gradient has no NaN (a mask after the exp passes
        inf * 0 = NaN back)."""
        b, s, h, p, n, g = 1, 256, 2, 8, 4, 1
        ks = jax.random.split(jax.random.PRNGKey(6), 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jnp.full((b, s, h), 0.5)
        A = jnp.asarray([-4.0, -16.0])     # cum falls by up to 2,048
        B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
        C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
        assert float(jnp.exp(-jnp.cumsum(dt * A[None, None], 1)).max()) \
            == float("inf")
        if impl == "pallas":
            y, st = ssd_chunked(x, dt, A, B, C, chunk=256, interpret=True)
            assert jnp.isfinite(y).all() and jnp.isfinite(st).all()
            return

        def loss(x, dt, A, B, C):
            y, st = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk=256)
            return jnp.sum(y ** 2) + jnp.sum(st ** 2)

        val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            x, dt, A, B, C)
        assert jnp.isfinite(val)
        for gr in grads:
            assert not jnp.isnan(gr).any()


class TestRowGather:
    @pytest.mark.parametrize("rows,d", [(16, 64), (64, 128), (8, 512)])
    def test_matches_ref(self, rows, d):
        src = jax.random.normal(jax.random.PRNGKey(0), (rows, d))
        idx = jax.random.randint(jax.random.PRNGKey(1), (32,), -1, rows)
        out = row_gather(src, idx, interpret=True)
        expect = row_gather_ref(src, idx)
        np.testing.assert_allclose(out, expect)

    def test_negative_idx_zeros(self):
        src = jnp.ones((4, 8))
        idx = jnp.array([-1, 0, -1, 3])
        out = row_gather(src, idx, interpret=True)
        np.testing.assert_array_equal(out[0], 0.0)
        np.testing.assert_array_equal(out[2], 0.0)
        np.testing.assert_array_equal(out[1], 1.0)


class TestBucketPack:
    def _roundtrip(self, sizes, tile=128):
        from repro.kernels.bucket_pack import (
            arena_from_leaves, bucket_pack_pallas, bucket_pack_ref,
            build_tile_tables)
        rng = np.random.default_rng(0)
        leaves = [jnp.asarray(rng.normal(size=(s,)), jnp.float32)
                  for s in sizes]
        arena, src_off = arena_from_leaves(leaves, tile=tile)
        # destination: dense tile-aligned concatenation (a bucket buffer)
        dst_off, cur = [], 0
        for s in sizes:
            dst_off.append(cur)
            cur += -(-s // tile) * tile
        padded = cur
        block, valid = build_tile_tables(src_off, dst_off, sizes, padded,
                                         tile=tile)
        out_k = bucket_pack_pallas(arena, jnp.asarray(block),
                                   jnp.asarray(valid), padded, tile=tile,
                                   interpret=True)
        out_r = bucket_pack_ref(arena, block, valid, padded, tile=tile)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        # semantic check: each segment equals its leaf, padding is zero
        for i, s in enumerate(sizes):
            seg = np.asarray(out_k[dst_off[i]: dst_off[i] + s])
            np.testing.assert_array_equal(seg, np.asarray(leaves[i]))
            tail = np.asarray(
                out_k[dst_off[i] + s: dst_off[i] + -(-s // tile) * tile])
            np.testing.assert_array_equal(tail, 0.0)
        return out_k

    @pytest.mark.parametrize("sizes", [[128], [100], [128, 256, 64],
                                       [1, 127, 129, 1000], [512] * 8])
    def test_shapes(self, sizes):
        self._roundtrip(sizes)

    def test_large_tile(self):
        self._roundtrip([2048, 77, 4096], tile=1024)


class TestPagedGather:
    """Paged-KV page gather: the jnp.take lowering vs the scalar oracle,
    over pool shapes and table patterns (unmapped entries, shared-nothing
    ownership, out-of-order pages)."""

    def _tables(self, rng, b, maxp, np_pages):
        # mapped entries draw WITHOUT replacement (allocator invariant:
        # unique ownership); ~1/3 of entries unmapped
        perm = rng.permutation(np_pages - 1) + 1  # page 0 = trash, unused
        table = np.full((b, maxp), -1, np.int32)
        k = 0
        for i in range(b):
            for p in range(maxp):
                if rng.random() < 0.67 and k < perm.size:
                    table[i, p] = perm[k]
                    k += 1
        return table

    LAYERS = 3

    @pytest.mark.parametrize("l", [0, 1, LAYERS - 1])
    @pytest.mark.parametrize("b,maxp,np_pages,ps,kv,hd", [
        (1, 2, 4, 4, 1, 4),
        (3, 4, 16, 8, 2, 8),
        (2, 3, 5, 2, 4, 16),
    ])
    def test_matches_oracle(self, b, maxp, np_pages, ps, kv, hd, l):
        # the stacked (L, NP, PS, KV, hd) pool, read at layer l: the same
        # pages as the oracle's gather of that layer's pool alone
        from repro.kernels.paged_kv import paged_gather_ref, paged_gather_take
        rng = np.random.default_rng(b * 100 + maxp)
        pool = jnp.asarray(
            rng.normal(size=(self.LAYERS, np_pages, ps, kv, hd)),
            jnp.float32)
        table = jnp.asarray(self._tables(rng, b, maxp, np_pages))
        layer = jnp.asarray(l, jnp.int32)
        out_t = paged_gather_take(pool, table, layer)
        out_r = paged_gather_ref(pool[l], table)
        assert out_t.shape == (b, maxp * ps, kv, hd)
        np.testing.assert_array_equal(np.asarray(out_t), np.asarray(out_r))

    @pytest.mark.parametrize("l", [0, LAYERS - 1])
    def test_row_pool_matches_oracle(self, l):
        # the (L, NP, PS, KV*hd) pool of heads narrower than a lane tile:
        # each token's heads one row, gathered as they lie
        from repro.kernels.paged_kv import paged_gather_ref, paged_gather_take
        b, maxp, np_pages, ps, e = 3, 4, 16, 8, 16
        rng = np.random.default_rng(7)
        pool = jnp.asarray(rng.normal(size=(self.LAYERS, np_pages, ps, e)),
                           jnp.float32)
        table = jnp.asarray(self._tables(rng, b, maxp, np_pages))
        layer = jnp.asarray(l, jnp.int32)
        out_r = paged_gather_ref(pool[l], table)
        assert out_r.shape == (b, maxp * ps, e)
        out = paged_gather_take(pool, table, layer)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_r))

    def test_unmapped_pages_zero(self):
        from repro.kernels.paged_kv import paged_gather_take
        # every page of layer l holds l + 1, so a zero can only come from
        # the unmapped-entry mask
        pool = (jnp.arange(1, 4, dtype=jnp.float32).reshape(3, 1, 1, 1, 1)
                * jnp.ones((3, 4, 2, 1, 2), jnp.float32))
        table = jnp.asarray([[-1, 2], [1, -1]], jnp.int32)
        layer = jnp.asarray(1, jnp.int32)
        out = np.asarray(paged_gather_take(pool, table, layer))
        np.testing.assert_array_equal(out[0, :2], 0.0)   # unmapped
        np.testing.assert_array_equal(out[0, 2:], 2.0)
        np.testing.assert_array_equal(out[1, :2], 2.0)
        np.testing.assert_array_equal(out[1, 2:], 0.0)

    def test_update_decode_writes_only_its_rows(self):
        # one decode token at layer l lands in exactly the B rows
        # [l, table[b, cur // PS], cur % PS] of the stacked pool (the trash
        # page 0 for an unmapped slot), bit for bit; nothing else moves
        from repro.models.attention import PagedKVLayer, paged_update_decode
        rng = np.random.default_rng(7)
        n_layers, np_pages, ps, kv, hd = 4, 9, 4, 2, 8
        table = np.asarray([[3, 5, -1], [1, 8, 2], [-1, -1, -1]], np.int32)
        b, cur, l = table.shape[0], 6, 2
        k = rng.normal(size=(n_layers, np_pages, ps, kv, hd)).astype(
            np.float32)
        v = rng.normal(size=k.shape).astype(np.float32)
        k_new = rng.normal(size=(b, 1, kv, hd)).astype(np.float32)
        v_new = rng.normal(size=(b, 1, kv, hd)).astype(np.float32)
        layer = PagedKVLayer(jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(table), jnp.asarray(cur, jnp.int32),
                             jnp.asarray(l, jnp.int32), ps)
        out = jax.jit(paged_update_decode)(layer, jnp.asarray(k_new),
                                           jnp.asarray(v_new))
        assert int(out.length) == cur + 1
        pages = [5, 8, 0]                    # table[:, 6 // 4], -1 -> trash
        for pool, new, old in ((out.k, k_new, k), (out.v, v_new, v)):
            want = old.copy()
            for i, page in enumerate(pages):
                want[l, page, cur % ps] = new[i, 0]
            got = np.asarray(pool)
            assert got.tobytes() == want.tobytes()
            assert (got != old).any(axis=(3, 4)).sum() == b


class TestPagedDecodeAttention:
    """The paged decode attention kernel (interpret mode) vs the gather
    and the contiguous decode attention it replaces on TPU, over both pool
    layouts, GQA groups, pool and query dtypes, layers of the stack, spans
    that start and end inside a page, and compute blocks of one page row
    or of two pages (walked across blocks and slots)."""

    LAYERS, PS, MAXP = 3, 4, 7

    def _case(self, rng, layout, groups, pool_dt, q_dt, kv=2, hd=16, b=4):
        np_pages = 1 + b * self.MAXP
        tail = (kv, hd) if layout == "heads" else (kv * hd,)
        shape = (self.LAYERS, np_pages, self.PS) + tail
        k = jnp.asarray(rng.normal(size=shape), pool_dt)
        v = jnp.asarray(rng.normal(size=shape), pool_dt)
        q = jnp.asarray(rng.normal(size=(b, kv * groups, hd)), q_dt)
        length = 5 * self.PS + 3                      # inside page 5
        start = np.asarray([1, 6, 9, 0], np.int32)[:b]   # inside pages
        table = np.full((b, self.MAXP), -1, np.int32)
        ids = iter(rng.permutation(np_pages - 1) + 1)
        for i in range(b):
            for p in range(start[i] // self.PS, self.MAXP):
                # the span's pages are mapped; a page past the cursor may be
                if p * self.PS < length or rng.random() < 0.5:
                    table[i, p] = next(ids)
        return q, k, v, jnp.asarray(table), jnp.asarray(start), length

    @staticmethod
    def _oracle(q, k, v, table, start, length, layer, scale):
        from repro.configs.base import ModelConfig
        from repro.kernels.paged_kv import paged_gather_take
        from repro.models.attention import KVCache, decode_attention
        hd = q.shape[-1]
        kv = paged_gather_take(k, table, layer)
        vv = paged_gather_take(v, table, layer)
        view = KVCache(kv.reshape(kv.shape[:2] + (-1, hd)),
                       vv.reshape(vv.shape[:2] + (-1, hd)),
                       jnp.asarray(length, jnp.int32))
        cfg = ModelConfig(name="t", family="dense", num_layers=1,
                          d_model=q.shape[1] * hd, num_heads=q.shape[1],
                          num_kv_heads=1, head_dim=hd, d_ff=1, vocab_size=1,
                          attention_multiplier=scale)
        return decode_attention(cfg, q[:, None], view, start=start)[:, 0]

    @pytest.mark.parametrize("blocks", ["row", "two_pages"])
    @pytest.mark.parametrize("pool_dt,q_dt", [
        (jnp.float32, jnp.float32), (jnp.float32, jnp.bfloat16),
        (jnp.bfloat16, jnp.bfloat16)])
    @pytest.mark.parametrize("groups", [1, 4])
    @pytest.mark.parametrize("layout", ["heads", "row"])
    def test_matches_gather_and_decode_attention(
            self, monkeypatch, layout, groups, pool_dt, q_dt, blocks):
        # the first, a middle and the last layer of the stack, read by one
        # compiled kernel that takes the layer at run time
        from repro.kernels import paged_kv
        if blocks == "two_pages":
            monkeypatch.setattr(paged_kv, "BLOCK_TOKENS", 2 * self.PS)
            monkeypatch.setattr(paged_kv, "BLOCK_BYTES", 0)
        rng = np.random.default_rng(groups * 10 + len(blocks))
        q, k, v, table, start, length = self._case(rng, layout, groups,
                                                   pool_dt, q_dt)
        scale = 0.3
        kernel = jax.jit(lambda layer: paged_kv.paged_decode_attention_pallas(
            q, k, v, table, start, length, layer, scale=scale,
            interpret=True))
        # f32 differs from the oracle only in the order of its sums; with
        # bf16 queries the kernel rounds unnormalised probabilities to
        # bf16 where the oracle rounds normalised ones
        tol = 2e-5 if q_dt == jnp.float32 else 2e-2
        for layer in range(self.LAYERS):
            got = kernel(jnp.int32(layer))
            want = self._oracle(q, k, v, table, start, length, layer, scale)
            assert got.shape == q.shape and got.dtype == q.dtype
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       rtol=tol, atol=tol)

    def test_unmapped_row_returns_zeros(self):
        # slot 1's row is all -1 (a finished slot): no page of it is live,
        # and it reads finite zeros where a guarded denominator is 0
        from repro.kernels.paged_kv import paged_decode_attention_pallas
        rng = np.random.default_rng(5)
        q, k, v, table, start, length = self._case(
            rng, "heads", 2, jnp.float32, jnp.float32)
        table = table.at[1].set(-1)
        got = np.asarray(paged_decode_attention_pallas(
            q, k, v, table, start, length, 1, scale=0.25, interpret=True))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[1], 0.0)
        assert (np.abs(got[[0, 2, 3]]).max(axis=(1, 2)) > 0).all()

    def test_pages_outside_the_span_are_never_attended(self):
        # NaN in every mapped page that lies wholly before a slot's start or
        # wholly past the cursor, at the layer read: a NaN that reached the
        # scores or the weighted sum would poison that slot's output
        from repro.kernels.paged_kv import paged_decode_attention_pallas
        rng = np.random.default_rng(9)
        q, k, v, table, start, length = self._case(
            rng, "row", 2, jnp.float32, jnp.float32)
        layer, ps = 2, self.PS
        tab = np.asarray(table).copy()
        for i in (1, 2):                   # map the page before the start's
            tab[i, start[i] // ps - 1] = 1 + self.MAXP * 4 + i
        tab = jnp.asarray(tab)
        outside = [(i, p) for i in range(tab.shape[0])
                   for p in range(self.MAXP)
                   if tab[i, p] >= 0 and ((p + 1) * ps <= start[i]
                                          or p * ps >= length)]
        assert len(outside) >= 3
        k = jnp.pad(k, ((0, 0), (0, 3), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 3), (0, 0), (0, 0)))
        for i, p in outside:
            k = k.at[layer, tab[i, p]].set(jnp.nan)
            v = v.at[layer, tab[i, p]].set(jnp.nan)
        got = paged_decode_attention_pallas(q, k, v, tab, start, length,
                                            layer, scale=0.25,
                                            interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        want = self._oracle(q, jnp.nan_to_num(k), jnp.nan_to_num(v), tab,
                            start, length, layer, 0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_unmapped_page_inside_the_span_is_not_attended(self):
        # slot 0 loses a page in the middle of its span: only the positions
        # of [start, length) on mapped pages are attended
        from repro.kernels.paged_kv import (paged_decode_attention_pallas,
                                            paged_gather_take)
        rng = np.random.default_rng(13)
        q, k, v, table, start, length = self._case(
            rng, "heads", 2, jnp.float32, jnp.float32)
        table = table.at[0, 2].set(-1)
        layer, scale, hd = 1, 0.25, q.shape[-1]
        got = paged_decode_attention_pallas(q, k, v, table, start, length,
                                            layer, scale=scale,
                                            interpret=True)
        kv = paged_gather_take(k, table, layer).reshape(4, -1, 2, hd)
        vv = paged_gather_take(v, table, layer).reshape(4, -1, 2, hd)
        pos = np.arange(kv.shape[1])
        ok = ((pos[None] >= np.asarray(start)[:, None]) & (pos[None] < length)
              & np.repeat(np.asarray(table) >= 0, self.PS, axis=1))
        kv, vv = np.repeat(kv, 2, axis=2), np.repeat(vv, 2, axis=2)
        s = np.einsum("bhd,bshd->bhs", q, kv) * scale
        s = np.where(ok[:, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("bhs,bshd->bhd", p / p.sum(-1, keepdims=True), vv)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)
