"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered from shapes and compiled for a
described (not attached) ``v5e:2x2`` topology, which catches what interpret
mode cannot (block shapes off the (8, 128) tiling, scalar-prefetch tables
larger than SMEM). The topology is described inside a fixture, never while
this module is imported: only one process at a time may load the TPU
library, and pytest-xdist workers import every test file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.bucket_pack import (MAX_TILES_PER_CALL, TILE,
                                       bucket_pack_pallas,
                                       bucket_unpack_pallas)
from repro.kernels.ops import flash_attention, row_gather, ssd_chunked
from repro.kernels.paged_kv import paged_decode_attention_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def on_chip(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def custom_call_results(hlo: str) -> list:
    """Result types of the ``tpu_custom_call``s in compiled HLO text."""
    return re.findall(r"= (\w+\[[\d,]*\])\S* custom-call\(.*"
                      r"custom_call_target=\"tpu_custom_call\"", hlo)


def named_kernel(hlo: str, name: str) -> bool:
    """Whether a ``tpu_custom_call`` of compiled HLO text is named
    ``name`` (the ``pallas_call``'s name, which the device trace shows)."""
    return re.search(rf"%{name}(\.\d+)? = .* custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"", hlo) is not None


def compile_paged_attention(sharding, q, pool, table, scale) -> str:
    """Compiled HLO of the paged decode attention kernel over a pool's
    shape; the slot starts, the cursor and the layer are run-time
    operands."""
    b = q[0][0]

    def fn(q, k, v, table, start, length, layer):
        return paged_decode_attention_pallas(q, k, v, table, start, length,
                                             layer, scale=scale)

    return compile_text(fn, sharding, q, pool, pool, table,
                        ((b,), jnp.int32), ((), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_gather(one_chip, dtype):
    # the paged decode step's attention (which replaced the page gather)
    # over the olmo-1b layer-stacked pool a batch-16 engine holds at
    # max_len 1024, in either cache dtype: the one custom call returns the
    # attention output, not a view of the pages
    cfg = get_config("olmo-1b")
    ps, maxp, b = 16, 64, 16
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pool = (cfg.num_layers, 1 + b * maxp, ps, kv, hd)
    hlo = compile_paged_attention(one_chip, ((b, h, hd), jnp.bfloat16),
                                  (pool, dtype), ((b, maxp), jnp.int32),
                                  hd ** -0.5)
    assert custom_call_results(hlo) == [f"bf16[{b},{h},{hd}]"]
    assert named_kernel(hlo, "paged_decode_attention")


def test_paged_gather_narrow_heads(one_chip):
    # the same over granite-4.0-h-micro's pool (4 attention layers, 8 KV
    # heads of 64 kept as one row of 512 a token, 32 query heads) at the
    # granite cell's batch 16, max_len 2048
    cfg = get_config("granite-4.0-h-micro")
    ps, maxp, b = 16, 128, 16
    h, hd = cfg.num_heads, cfg.head_dim
    pool = (4, 1 + b * maxp, ps, cfg.num_kv_heads * hd)
    hlo = compile_paged_attention(one_chip, ((b, h, hd), jnp.bfloat16),
                                  (pool, jnp.float32), ((b, maxp), jnp.int32),
                                  cfg.attention_multiplier)
    assert custom_call_results(hlo) == [f"bf16[{b},{h},{hd}]"]
    assert named_kernel(hlo, "paged_decode_attention")


def test_paged_serve_step_keeps_pool_in_place(one_chip, monkeypatch):
    # the olmo-1b decode step at the chat cell's sizes (batch 16, max_len
    # 256, pages of 16, float32 pool), cache donated as the engine does:
    # the layer scan carries the stacked pool and writes it in place, so
    # the step needs less scratch than one layer's K pool (a whole-pool
    # slice, update or copy would need at least that)
    import repro.kernels.paged_kv as paged_kv
    from repro.models.transformer import init_paged_cache, init_params
    from repro.serve.engine import make_serve_step

    monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    cfg = get_config("olmo-1b")
    b, max_len, ps = 16, 256, 16
    num_pages = 1 + b * (max_len // ps)
    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k),
                                    jax.random.key(0)), one_chip)
    cache = on_chip(jax.eval_shape(lambda: init_paged_cache(
        cfg, b, max_len, page_size=ps, num_pages=num_pages,
        dtype=jnp.float32)), one_chip)
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    start = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    temps = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one_chip)
    step = jax.jit(make_serve_step(cfg), donate_argnums=(2,))
    compiled = step.lower(params, tokens, cache, start, temps, key).compile()
    layer_pool = num_pages * ps * cfg.num_kv_heads * cfg.head_dim * 4
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool
    # no custom call returns a view of the pages: the one in the step is
    # the paged attention kernel, which returns the attention output
    hlo = compiled.as_text()
    out = f"bf16[{b},{cfg.num_heads},{cfg.head_dim}]"
    assert custom_call_results(hlo) == [out]
    assert named_kernel(hlo, "paged_decode_attention")


@pytest.mark.parametrize("kernel", [bucket_pack_pallas, bucket_unpack_pallas])
def test_bucket_pack_unpack(one_chip, kernel):
    # the unpack over a 4-layer olmo-1b gradient arena: 363,520 tiles, whose
    # tables outgrow SMEM unless the call is split
    n = 363_520 * TILE
    assert n // TILE > MAX_TILES_PER_CALL

    def fn(src, block, valid):
        return kernel(src, block, valid, n, tile=TILE)

    hlo = compile_text(fn, one_chip, ((n,), jnp.float32),
                       ((n // TILE,), jnp.int32), ((n // TILE,), jnp.int32))
    assert hlo.count("tpu_custom_call") >= -(-n // TILE // MAX_TILES_PER_CALL)


def test_ssd_chunked(one_chip):
    cfg = get_config("mamba2-780m")
    c = cfg.ssm
    b, s = 1, 2 * c.chunk_size
    h, p = c.num_heads(cfg.d_model), c.head_dim
    g, n = c.ngroups, c.d_state
    fn = functools.partial(ssd_chunked, chunk=c.chunk_size, interpret=False)
    hlo = compile_text(fn, one_chip, ((b, s, h, p), jnp.bfloat16),
                       ((b, s, h), jnp.float32), ((h,), jnp.float32),
                       ((b, s, g, n), jnp.bfloat16),
                       ((b, s, g, n), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_flash_attention(one_chip):
    cfg = get_config("olmo-1b")
    q = (1, cfg.num_heads, 2048, cfg.head_dim)
    kv = (1, cfg.num_kv_heads, 2048, cfg.head_dim)
    fn = functools.partial(flash_attention, interpret=False)
    hlo = compile_text(fn, one_chip, (q, jnp.bfloat16), (kv, jnp.bfloat16),
                       (kv, jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_row_gather(one_chip):
    # mixtral-8x22b token rows into top-2 expert slots
    cfg = get_config("mixtral-8x22b")
    t = 4096
    fn = functools.partial(row_gather, interpret=False)
    hlo = compile_text(fn, one_chip, ((t, cfg.d_model), jnp.bfloat16),
                       ((2 * t,), jnp.int32))
    assert "tpu_custom_call" in hlo
