"""Fast bucketed-reduction path: kernels, plan cache, and reduction modes.

Covers the three tentpole pieces of the fast path:

* ``bucket_pack_pallas`` / ``bucket_unpack_pallas`` round-trip against the
  jnp oracles in interpret mode (plus the vectorized gather lowering);
* ``get_comm_plan`` persistent-cache hit/reuse semantics;
* ``reduce_gradients`` pack/reduction knob equivalence (single-device mesh
  here; the 8-device numerics live in tests/_multidev_checks.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (
    get_comm_plan,
    plan_buckets,
    plan_cache_clear,
    plan_cache_stats,
    reduce_gradients,
)
from repro.core.bucketing import _pack_bucket_dma, pack_bucket, unpack_bucket
from repro.kernels import bucket_pack
from repro.kernels.bucket_pack import (
    arena_from_leaves,
    arena_layout,
    bucket_pack_gather,
    bucket_pack_pallas,
    bucket_pack_ref,
    bucket_unpack_gather,
    bucket_unpack_pallas,
    bucket_unpack_ref,
    build_tile_tables,
)

TILE = 16  # small tile: interpret mode grid-steps in Python


def _tree(shapes, dtype=jnp.float32):
    return {f"leaf{i}": (jnp.arange(int(np.prod(s)), dtype=dtype)
                         .reshape(s) * (i + 1))
            for i, s in enumerate(shapes)}


def _plan_tables(tree, nb, tile=TILE):
    """(plan, arena, per-bucket pack tables, unpack table, arena meta)."""
    plan = plan_buckets(tree, nb, align=tile, slot_align=tile)
    leaves = jax.tree_util.tree_leaves(tree)
    sizes = [l.size for l in leaves]
    arena_offs, arena_size = arena_layout(sizes, tile)
    arena, offs = arena_from_leaves(leaves, tile=tile, dtype=jnp.float32)
    np.testing.assert_array_equal(offs, arena_offs)
    assert arena.shape[0] == arena_size
    pack_tables = [build_tile_tables(
        [arena_offs[s.index] for s in b.slots],
        [s.offset for s in b.slots],
        [s.size for s in b.slots], b.padded_size, tile)
        for b in plan.buckets]
    bases = np.cumsum([0] + [b.padded_size for b in plan.buckets])
    src, dst, szs = [], [], []
    for bi, b in enumerate(plan.buckets):
        for s in b.slots:
            src.append(int(bases[bi]) + s.offset)
            dst.append(int(arena_offs[s.index]))
            szs.append(s.size)
    unpack_table = build_tile_tables(src, dst, szs, arena_size, tile)
    return plan, leaves, arena, pack_tables, unpack_table, arena_offs, arena_size


class TestPallasKernels:
    SHAPES = [
        [(7,), (33,), (4, 5)],
        [(1,)],
        [(16,), (16,), (16,), (3, 3, 3)],
        [(100,), (2,), (50,)],
    ]

    @pytest.mark.parametrize("shapes", SHAPES)
    @pytest.mark.parametrize("nb", [1, 2])
    def test_pack_kernel_matches_oracle(self, shapes, nb):
        tree = _tree(shapes)
        _, _, arena, pack_tables, _, _, _ = _plan_tables(tree, nb)
        for (blk, val), b in zip(pack_tables,
                                 plan_buckets(tree, nb, align=TILE,
                                              slot_align=TILE).buckets):
            out_k = bucket_pack_pallas(arena, jnp.asarray(blk),
                                       jnp.asarray(val), b.padded_size,
                                       tile=TILE, interpret=True)
            out_r = bucket_pack_ref(arena, blk, val, b.padded_size, tile=TILE)
            out_g = bucket_pack_gather(arena, blk, val, b.padded_size,
                                       tile=TILE)
            np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
            np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_r))

    @pytest.mark.parametrize("shapes", SHAPES)
    def test_pack_unpack_roundtrip_interpret(self, shapes):
        """arena -> per-bucket pack -> concat -> unpack == arena."""
        tree = _tree(shapes)
        plan, leaves, arena, pack_tables, unpack_table, arena_offs, \
            arena_size = _plan_tables(tree, 2)
        packed = [bucket_pack_pallas(arena, jnp.asarray(t[0]),
                                     jnp.asarray(t[1]), b.padded_size,
                                     tile=TILE, interpret=True)
                  for t, b in zip(pack_tables, plan.buckets)]
        allp = jnp.concatenate(packed) if len(packed) > 1 else packed[0]
        out_k = bucket_unpack_pallas(allp, jnp.asarray(unpack_table[0]),
                                     jnp.asarray(unpack_table[1]),
                                     arena_size, tile=TILE, interpret=True)
        out_r = bucket_unpack_ref(allp, *unpack_table, arena_size, tile=TILE)
        out_g = bucket_unpack_gather(allp, *unpack_table, arena_size,
                                     tile=TILE)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_r))
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(arena))
        # and each leaf slices back exactly
        for i, leaf in enumerate(leaves):
            off = int(arena_offs[i])
            got = out_k[off: off + leaf.size].reshape(leaf.shape)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(leaf))

    @pytest.mark.parametrize("shapes", SHAPES)
    def test_split_calls_match_oracle(self, shapes, monkeypatch):
        """A buffer longer than one call's tile tables is written chunk by
        chunk into one aliased output; every chunk lands in place."""
        monkeypatch.setattr(bucket_pack, "MAX_TILES_PER_CALL", 2)
        tree = _tree(shapes)
        _, _, arena, _, unpack_table, _, arena_size = _plan_tables(tree, 2)
        blk, val = unpack_table
        out_k = bucket_unpack_pallas(arena, jnp.asarray(blk),
                                     jnp.asarray(val), arena_size, tile=TILE,
                                     interpret=True)
        out_r = bucket_unpack_ref(arena, blk, val, arena_size, tile=TILE)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    def test_dma_pack_matches_concat_pack(self):
        """The non-TPU DUS lowering == pack_bucket on slot-aligned plans."""
        tree = _tree([(7,), (40,), (3, 9), (2,)])
        plan = plan_buckets(tree, 2, align=TILE, slot_align=TILE)
        leaves = jax.tree_util.tree_leaves(tree)
        for b in plan.buckets:
            dma = _pack_bucket_dma(leaves, b, jnp.float32)
            ref = pack_bucket(leaves, b, dtype=jnp.float32)
            np.testing.assert_array_equal(np.asarray(dma), np.asarray(ref))

    def test_slot_aligned_plan_layout(self):
        tree = _tree([(5,), (17,), (100,)])
        plan = plan_buckets(tree, 2, align=TILE, slot_align=TILE)
        for b in plan.buckets:
            assert b.padded_size % TILE == 0
            for s in b.slots:
                assert s.offset % TILE == 0
        # roundtrip through pack/unpack still exact with gap padding
        leaves = jax.tree_util.tree_leaves(tree)
        rec = {}
        for b in plan.buckets:
            flat = pack_bucket(leaves, b)
            for idx, val in unpack_bucket(flat, b):
                rec[idx] = val
        for i, leaf in enumerate(leaves):
            np.testing.assert_array_equal(np.asarray(rec[i]), np.asarray(leaf))


class TestPlanCache:
    def setup_method(self):
        plan_cache_clear()

    def teardown_method(self):
        plan_cache_clear()

    def _grads(self, n=5, base=8):
        return {f"g{i}": jnp.ones((base + i,)) for i in range(n)}

    def test_hit_returns_same_object(self):
        g = self._grads()
        a = get_comm_plan(g, num_streams=2)
        b = get_comm_plan(g, num_streams=2)
        assert a is b
        s = plan_cache_stats()
        assert s["hits"] == 1 and s["misses"] == 1 and s["builds"] == 1

    def test_key_includes_shapes_and_knobs(self):
        a = get_comm_plan(self._grads(), num_streams=2)
        b = get_comm_plan(self._grads(base=9), num_streams=2)   # new shapes
        c = get_comm_plan(self._grads(), num_streams=3)         # new knob
        d = get_comm_plan(self._grads(), num_streams=2, pack="pallas")
        assert len({id(x) for x in (a, b, c, d)}) == 4
        assert plan_cache_stats()["size"] == 4

    def test_non_persistent_bypasses_cache(self):
        g = self._grads()
        a = get_comm_plan(g, num_streams=2, persistent=False)
        b = get_comm_plan(g, num_streams=2, persistent=False)
        assert a is not b
        s = plan_cache_stats()
        assert s["size"] == 0 and s["builds"] == 2 and s["hits"] == 0

    def test_plan_contexts_cover_buckets(self):
        cp = get_comm_plan(self._grads(), num_streams=3)
        assert len(cp.contexts) == cp.plan.num_buckets
        assert len({c.name for c in cp.contexts}) == len(cp.contexts)

    def test_runtime_is_fresh_per_call(self):
        """Tokens are trace-local: each trace must get its own engine."""
        cp = get_comm_plan(self._grads(), num_streams=2)
        assert cp.runtime() is not cp.runtime()
        assert cp.runtime().world is cp.world

    def test_pallas_tables_cached_once(self):
        cp = get_comm_plan(self._grads(), num_streams=2, pack="pallas")
        t1 = cp.tables
        t2 = cp.tables
        assert t1 is t2
        tile, offs, size, pack_tables, unpack_table = t1
        assert size % tile == 0
        assert len(pack_tables) == cp.plan.num_buckets


class TestPlanCacheTrainStep:
    """CommPlan cache behaviour through the REAL train_step, for both
    comm schedules: repeated (eager, hence re-traced) steps HIT the cache;
    a knob change (num_streams), a schedule change, and a shape change
    (different arch => different grad shapes) each MISS and build anew."""

    def setup_method(self):
        plan_cache_clear()

    def teardown_method(self):
        plan_cache_clear()

    @staticmethod
    def _step_and_state(cfg, mesh, *, schedule, num_streams=2):
        from repro.train.trainer import make_train_step, train_state_init

        step = make_train_step(cfg, mesh=mesh, comm="vci",
                               num_streams=num_streams, num_vcis=2,
                               token_impl="data", schedule=schedule)
        state = train_state_init(cfg, jax.random.PRNGKey(0), mesh=mesh,
                                 num_streams=num_streams, schedule=schedule)
        return step, state

    @pytest.mark.parametrize("schedule", ["post", "overlap"])
    def test_repeated_steps_hit_then_knob_and_shape_miss(self, schedule):
        from repro.configs import get_config
        from repro.data.pipeline import synthetic_batch

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        cfg = get_config("olmo-1b-smoke")
        step, state = self._step_and_state(cfg, mesh, schedule=schedule)
        plan_cache_clear()
        with jax.set_mesh(mesh):
            # eager (unjitted) calls re-trace every step: each trace asks
            # for the plan again, so steps 2..3 must hit the cache.
            for i in range(3):
                state, _ = step(state, synthetic_batch(cfg, 2, 16, seed=i))
        s = plan_cache_stats()
        assert s["misses"] == 1 and s["builds"] == 1, s
        assert s["hits"] == 2 and s["size"] == 1, s

        # knob change: same tree, different num_streams -> new plan
        step3, state3 = self._step_and_state(cfg, mesh, schedule=schedule,
                                             num_streams=3)
        with jax.set_mesh(mesh):
            step3(state3, synthetic_batch(cfg, 2, 16, seed=0))
        s = plan_cache_stats()
        assert s["misses"] == 2 and s["size"] == 2, s

        # shape change: different arch -> different grad shapes -> new plan
        cfg2 = get_config("gemma-2b-smoke")
        step_g, state_g = self._step_and_state(cfg2, mesh, schedule=schedule)
        with jax.set_mesh(mesh):
            step_g(state_g, synthetic_batch(cfg2, 2, 16, seed=0))
        s = plan_cache_stats()
        assert s["misses"] == 3 and s["builds"] == 3 and s["size"] == 3, s

    def test_schedules_key_separate_plans(self):
        """post and overlap must never share a cached plan: the overlap
        partition is contiguous-by-use-order, post is size-balanced."""
        from repro.configs import get_config
        from repro.data.pipeline import synthetic_batch

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        cfg = get_config("olmo-1b-smoke")
        batch = synthetic_batch(cfg, 2, 16, seed=0)
        for schedule in ("post", "overlap"):
            step, state = self._step_and_state(cfg, mesh, schedule=schedule)
            with jax.set_mesh(mesh):
                step(state, batch)
        s = plan_cache_stats()
        assert s["misses"] == 2 and s["builds"] == 2 and s["size"] == 2, s
        assert s["hits"] == 0, s


class TestReducePaths:
    """Single-device mesh: the reduction is the identity (axis size 1), so
    every pack/reduction combination must reproduce the input tree."""

    def setup_method(self):
        plan_cache_clear()

    @pytest.mark.parametrize("pack", ["xla", "pallas"])
    @pytest.mark.parametrize("reduction", ["all_reduce", "reduce_scatter"])
    def test_identity_on_one_device(self, pack, reduction):
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        tree = _tree([(4, 8), (130,), (3,)])
        spec = jax.tree_util.tree_map(lambda _: P(), tree)

        def run(tr):
            cp = get_comm_plan(tr, num_streams=2, num_vcis=3, pack=pack)
            rt = cp.runtime()
            return reduce_gradients(rt, tr, cp, axis="data", mean=True,
                                    pack=pack, reduction=reduction)

        f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(spec,),
                                  out_specs=spec, check_vma=False))
        got = f(tree)
        for g, e in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       rtol=1e-6)

    def test_bad_knobs_raise(self):
        cp = get_comm_plan(_tree([(4,)]), num_streams=1)
        with pytest.raises(ValueError):
            reduce_gradients(cp.runtime(), _tree([(4,)]), cp, pack="nope")
        with pytest.raises(ValueError):
            reduce_gradients(cp.runtime(), _tree([(4,)]), cp,
                             reduction="nope")
