"""The unified model over all assigned architecture families.

One ``Model`` class covers: dense GQA/MQA transformers (gemma/yi/command-r/
olmo), MoE (mixtral/arctic), SSM (mamba2), hybrids with a per-layer mixer
pattern (granite-4.0-h: Mamba-2 and attention layers, an MLP in each), the
hybrid with a shared attention block (zamba2), VLM (phi-3-vision: stubbed
patch embeddings spliced before text) and audio (musicgen: 4 EnCodec
codebook streams, summed embeddings, one LM head per codebook).

Layers are stacked along a leading axis, one stack per kind of weight, and
executed with ``lax.scan`` over periods of the mixer pattern (one layer for
all but the pattern hybrids; compile-time control for 512-device
dry-runs). zamba2 scans groups of ``hybrid_attn_every`` SSM blocks followed
by ONE shared-weight attention block (its parameter-sharing trick — the
weights are shared, but each application site keeps its own KV cache).
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (
    KVCache,
    PagedKVCache,
    PagedKVLayer,
    attention,
    cache_update_decode,
    decode_attention,
    paged_decode_attention,
    paged_prefill_update,
    paged_update_decode,
)
from repro.models.layers import (
    apply_norm,
    apply_rope,
    dense_init,
    embed_init,
    gated_ffn,
    maybe_bf16_grads,
)
from repro.models.moe import moe_ffn
from repro.models.ssm import SSMState, mamba2_decode, mamba2_forward

IMG_EMBED_DIM = 1024  # stubbed CLIP patch-embedding width (phi-3-vision)


def _remat_policy(cfg: ModelConfig):
    """remat="block" recomputes everything (incl. the forward TP
    all-reduces); remat="dots" is selective activation recomputation —
    matmul outputs (already all-reduced) are saved, so the backward never
    re-runs forward collectives. EXPERIMENTS.md §Perf."""
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    return None


# ---------------------------------------------------------------------------
# parameter initialization
# ---------------------------------------------------------------------------

def _norm_params(cfg: ModelConfig, dims: Tuple[int, ...], d: Optional[int] = None):
    if cfg.norm == "nonparametric":
        return None
    d = cfg.d_model if d is None else d
    p = {"scale": jnp.ones(dims + (d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(dims + (d,), jnp.float32)
    return p


def _attn_params(cfg: ModelConfig, key, dims: Tuple[int, ...], dtype):
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": dense_init(ks[0], dims + (d, qd), dtype=dtype),
        "wk": dense_init(ks[1], dims + (d, kvd), dtype=dtype),
        "wv": dense_init(ks[2], dims + (d, kvd), dtype=dtype),
        "wo": dense_init(ks[3], dims + (qd, d), dtype=dtype),
    }
    if cfg.use_bias:
        p |= {
            "bq": jnp.zeros(dims + (qd,), dtype),
            "bk": jnp.zeros(dims + (kvd,), dtype),
            "bv": jnp.zeros(dims + (kvd,), dtype),
            "bo": jnp.zeros(dims + (d,), dtype),
        }
    return p


def _ffn_params(cfg: ModelConfig, key, dims: Tuple[int, ...], dtype, dff=None):
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    dff = cfg.d_ff if dff is None else dff
    p = {
        "w_gate": dense_init(ks[0], dims + (d, dff), dtype=dtype),
        "w_up": dense_init(ks[1], dims + (d, dff), dtype=dtype),
        "w_down": dense_init(ks[2], dims + (dff, d), dtype=dtype),
    }
    if cfg.use_bias:
        p |= {"b_up": jnp.zeros(dims + (dff,), dtype),
              "b_down": jnp.zeros(dims + (d,), dtype)}
    return p


def _ssm_params(cfg: ModelConfig, key, dims: Tuple[int, ...], dtype):
    c = cfg.ssm
    d = cfg.d_model
    d_in = c.d_inner(d)
    nh = c.num_heads(d)
    d_bc = 2 * c.ngroups * c.d_state
    proj_out = 2 * d_in + d_bc + nh
    ks = jax.random.split(key, 3)
    # dt bias initialized so softplus(dt_bias) spans [1e-3, 1e-1] (mamba2 init)
    u = jax.random.uniform(ks[2], dims + (nh,), jnp.float32)
    dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    a_init = jnp.broadcast_to(
        jnp.log(jnp.linspace(1.0, 16.0, nh)), dims + (nh,))
    p = {
        "in_proj": dense_init(ks[0], dims + (d, proj_out), dtype=dtype),
        "conv_w": 0.1 * jax.random.normal(ks[1], dims + (c.conv_width, d_in + d_bc),
                                          jnp.float32).astype(dtype),
        "A_log": a_init.astype(jnp.float32),
        "D": jnp.ones(dims + (nh,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "gate_norm": jnp.ones(dims + (d_in,), jnp.float32),
        "out_proj": dense_init(ks[0], dims + (d_in, d), dtype=dtype),
    }
    if c.conv_bias:
        p["conv_b"] = jnp.zeros(dims + (d_in + d_bc,), dtype)
    return p


def _moe_params(cfg: ModelConfig, key, dims: Tuple[int, ...], dtype):
    m = cfg.moe
    ks = jax.random.split(key, 5)
    d, ff, e = cfg.d_model, cfg.d_ff, m.num_experts
    p = {
        "router": dense_init(ks[0], dims + (d, e), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], dims + (e, d, ff), dtype=dtype),
        "w_up": dense_init(ks[2], dims + (e, d, ff), dtype=dtype),
        "w_down": dense_init(ks[3], dims + (e, ff, d), dtype=dtype),
    }
    if m.dense_residual:
        p["residual"] = _ffn_params(cfg, ks[4], dims, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    L = cfg.num_layers
    params: Dict[str, Any] = {}

    if cfg.modality == "audio":
        params["embed"] = {"tok": embed_init(
            keys[0], (cfg.num_codebooks, cfg.vocab_size, cfg.d_model), dtype)}
    else:
        params["embed"] = {"tok": embed_init(
            keys[0], (cfg.vocab_size, cfg.d_model), dtype)}
    if cfg.modality == "vlm":
        params["img_proj"] = {"w": dense_init(
            keys[1], (IMG_EMBED_DIM, cfg.d_model), dtype=dtype)}

    # one stack per kind of weight: "attn" over the attention layers, "ssm"
    # over the Mamba-2 layers, norms and MLPs over every layer
    dims = (L,)
    layer = {"norm1": _norm_params(cfg, dims)}
    mixers = cfg.mixers
    if mixers is None:  # zamba2: Mamba-2 blocks and ONE shared block
        layer["ssm"] = _ssm_params(cfg, keys[2], dims, dtype)
        params["shared_attn"] = {
            "attn": _attn_params(cfg, keys[3], (), dtype),
            "ffn": _ffn_params(cfg, keys[4], (), dtype),
            "norm1": _norm_params(cfg, ()),
            "norm2": _norm_params(cfg, ()),
        }
    else:
        n_attn = mixers.count("attention")
        if n_attn:
            layer["attn"] = _attn_params(cfg, keys[2], (n_attn,), dtype)
        if n_attn < L:
            layer["ssm"] = _ssm_params(cfg, keys[6] if n_attn else keys[2],
                                       (L - n_attn,), dtype)
        if cfg.moe is not None:
            layer["moe"] = _moe_params(cfg, keys[3], dims, dtype)
        elif cfg.d_ff:
            layer["ffn"] = _ffn_params(cfg, keys[3], dims, dtype)
        if cfg.d_ff and not cfg.parallel_block:
            layer["norm2"] = _norm_params(cfg, dims)
    params["layers"] = {k: v for k, v in layer.items() if v is not None}

    fn = _norm_params(cfg, ())
    if fn is not None:
        params["final_norm"] = fn
    if not cfg.tie_embeddings:
        if cfg.modality == "audio":
            params["lm_head"] = {"w": dense_init(
                keys[5], (cfg.num_codebooks, cfg.d_model, cfg.vocab_size),
                dtype=dtype)}
        else:
            params["lm_head"] = {"w": dense_init(
                keys[5], (cfg.d_model, cfg.vocab_size), dtype=dtype)}
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    """Per-arch decode state, layer-stacked along the leading axis: K/V of
    the attention layers (or sites) and the recurrent state of the Mamba-2
    layers, each with a row per batch slot."""

    kv: Optional[KVCache]       # (n_attn, B, S, KV, hd) stacked, or paged
    ssm: Optional[SSMState]     # (n_mamba, B, ...) stacked
    length: jax.Array           # () int32 — absolute tokens decoded


def _layer_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(layers or sites with a K/V cache, layers with Mamba-2 state)."""
    mixers = cfg.mixers
    if mixers is None:  # zamba2: every block Mamba-2, shared-attention sites
        return cfg.num_layers // cfg.hybrid_attn_every, cfg.num_layers
    n_attn = mixers.count("attention")
    return n_attn, cfg.num_layers - n_attn


def _stack(tree, n):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def _ssm_cache(cfg: ModelConfig, batch: int, dtype) -> Optional[SSMState]:
    """Zeroed Mamba-2 state for every Mamba-2 layer: the SSD state in
    float32, the conv window in the cache dtype."""
    n = _layer_counts(cfg)[1]
    if not n:
        return None
    st = _stack(SSMState.init(cfg, batch, dtype=jnp.float32), n)
    return SSMState(st.conv.astype(dtype), st.ssd)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> DecodeCache:
    if "kv_fp8" in cfg.opts and jnp.dtype(dtype) == jnp.bfloat16:
        # OPT(kv_fp8): fp8 KV storage — halves the decode memory-roofline
        # term (EXPERIMENTS §Perf); dequantized at attention read.
        dtype = jnp.float8_e4m3fn
    n_attn = _layer_counts(cfg)[0]
    kv = None
    if n_attn:
        kv0 = KVCache.init(cfg, batch, max_len, dtype)
        kv = KVCache(_stack(kv0.k, n_attn), _stack(kv0.v, n_attn),
                     kv0.length, kv0.ring)
    return DecodeCache(kv, _ssm_cache(cfg, batch, dtype),
                       jnp.zeros((), jnp.int32))


def has_paged_layout(cfg: ModelConfig, max_len: int) -> bool:
    """Whether ``cfg`` has a paged decode cache at depth ``max_len``: a text
    model of one mixer per layer (dense, MoE, SSM and the per-layer pattern
    hybrids) with no ring cache. A ring (sliding window under ``max_len``)
    reuses slots modulo the window, zamba2's shared attention block has no
    per-layer stack, and the VLM/audio frontends take no per-row starts."""
    return (cfg.mixers is not None and cfg.modality == "text"
            and (cfg.sliding_window is None
                 or cfg.sliding_window >= max_len))


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int, num_pages: int,
                     dtype=jnp.bfloat16) -> DecodeCache:
    """Paged decode cache: a fixed pool of ``num_pages`` pages of
    ``page_size`` tokens (page 0 reserved as trash) for each attention
    layer + an all-unmapped per-slot page table covering virtual positions
    ``[0, max_len)``, and beside it a per-slot recurrent state for each
    Mamba-2 layer (the state has no positions, so no pages). A page holds
    ``(page_size, KV, hd)``, or ``(page_size, KV*hd)`` for heads narrower
    than 128. Only for configs :func:`has_paged_layout` accepts.
    """
    if not has_paged_layout(cfg, max_len):
        raise NotImplementedError(
            f"no paged layout for family={cfg.family!r} modality="
            f"{cfg.modality!r} sliding_window={cfg.sliding_window} at "
            f"max_len={max_len}; init_cache holds its contiguous cache")
    if page_size < 1 or num_pages < 2:
        raise ValueError(f"need page_size >= 1 and num_pages >= 2 "
                         f"(page 0 is the trash page), got "
                         f"{page_size}/{num_pages}")
    if "kv_fp8" in cfg.opts and jnp.dtype(dtype) == jnp.bfloat16:
        dtype = jnp.float8_e4m3fn  # OPT(kv_fp8): see init_cache
    kvh = cfg.num_kv_heads * max(1, cfg.decode_kv_expand)
    max_pages = -(-max_len // page_size)
    # a token's heads: (KV, hd), or one row of KV*hd where a head is
    # narrower than a lane tile (128), whose (KV, hd) pages the TPU would
    # lay out otherwise than the kernels read them
    heads = ((kvh, cfg.head_dim) if cfg.head_dim % 128 == 0
             else (kvh * cfg.head_dim,))
    shape = (_layer_counts(cfg)[0], num_pages, page_size) + heads
    kv = PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                      jnp.full((batch, max_pages), -1, jnp.int32),
                      jnp.zeros((), jnp.int32), page_size)
    return DecodeCache(kv, _ssm_cache(cfg, batch, dtype),
                       jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _layer_kv(kv, l: int):
    """Layer ``l``'s view of a stacked (contiguous or paged) KV cache; the
    paged view names the whole pool and the layer."""
    if isinstance(kv, PagedKVCache):
        return PagedKVLayer(kv.k, kv.v, kv.table, kv.length, l, kv.page_size)
    return KVCache(kv.k[l], kv.v[l], kv.length, kv.ring)


def _set_layer_kv(kv, l: int, new):
    """The stacked cache with layer ``l``'s updated view written back; the
    cursor stays where it was (it moves once, after the last layer)."""
    if isinstance(kv, PagedKVCache):  # the view's pool already holds it
        return PagedKVCache(new.k, new.v, kv.table, kv.length, kv.page_size)
    return KVCache(kv.k.at[l].set(new.k), kv.v.at[l].set(new.v), kv.length,
                   kv.ring)


def _advance_kv(kv, advanced: int):
    """``kv`` with its cursor moved ``advanced`` tokens (S for prefill, 1 for
    decode)."""
    if isinstance(kv, PagedKVCache):
        return PagedKVCache(kv.k, kv.v, kv.table, kv.length + advanced,
                            kv.page_size)
    return KVCache(kv.k, kv.v, kv.length + advanced, kv.ring)


def _attn_apply(cfg: ModelConfig, x, p, positions, shard,
                kv: Optional[KVCache] = None, decode: bool = False,
                comm=None, start=None):
    """``comm`` (repro.serve.comm.ServeComm) selects manual TP: weights
    arrive Megatron-sharded, head dims below are LOCAL counts, and the
    row-parallel ``wo`` partial sum is all-reduced on the ``tp_attn`` VCI
    stream. ``start`` is the per-row left-pad offset (serve engine)."""
    b, s, d = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # -1 head counts: under manual TP each rank holds num_heads/tp heads.
    q = q.reshape(b, s, -1, cfg.head_dim)
    k = k.reshape(b, s, -1, cfg.head_dim)
    v = v.reshape(b, s, -1, cfg.head_dim)
    if cfg.position_embedding_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if shard is not None:
        q = shard.heads(q)

    new_kv = None
    if decode:
        if isinstance(kv, PagedKVLayer):
            new_kv = paged_update_decode(kv, k, v)
            o = paged_decode_attention(cfg, q, new_kv, start=start)
        else:
            new_kv = cache_update_decode(kv, k, v)
            if shard is not None:
                new_kv = KVCache(shard.kv_cache(new_kv.k),
                                 shard.kv_cache(new_kv.v),
                                 new_kv.length, new_kv.ring)
            o = decode_attention(cfg, q, new_kv, start=start)
    else:
        o = attention(cfg, q, k, v, start=start)
        if isinstance(kv, PagedKVLayer):  # prefill: write the page pool
            new_kv = paged_prefill_update(kv, k, v)
        elif kv is not None:              # prefill: write the cache
            new_kv = _prefill_cache(kv, k, v)
    o = o.reshape(b, s, -1)
    o = o @ p["wo"].astype(o.dtype)
    if comm is not None:
        o = comm.psum(o, "tp_attn")
    if cfg.use_bias:
        o = o + p["bo"]
    return o, new_kv


def _prefill_cache(kv: KVCache, k, v) -> KVCache:
    from repro.models.attention import _expand_to_cache
    k = _expand_to_cache(kv, k)
    v = _expand_to_cache(kv, v)
    s = k.shape[1]
    s_cache = kv.k.shape[1]
    if kv.ring and s > s_cache:
        # keep the last W tokens, placed to satisfy the ring invariant
        # (slot i holds absolute position ≡ i mod W)
        k, v = k[:, -s_cache:], v[:, -s_cache:]
        shift = s % s_cache
        if shift:
            k = jnp.roll(k, shift, axis=1)
            v = jnp.roll(v, shift, axis=1)
    n = min(s, s_cache)
    kc = jax.lax.dynamic_update_slice(kv.k, k[:, :n].astype(kv.k.dtype), (0, 0, 0, 0))
    vc = jax.lax.dynamic_update_slice(kv.v, v[:, :n].astype(kv.v.dtype), (0, 0, 0, 0))
    return KVCache(kc, vc, kv.length + s, kv.ring)


def _scaled(cfg: ModelConfig, y):
    """A residual branch times ``residual_multiplier`` (granite's muP)."""
    m = cfg.residual_multiplier
    return y if m == 1.0 else y * m


def _block(cfg: ModelConfig, x, p, positions, shard, *, mixer="attention",
           kv=None, state=None, decode=False, comm=None, start=None):
    """One layer: the pre-norm token mixer (attention, or Mamba-2 over the
    recurrent ``state``), then the pre-norm MLP (dense or MoE) where the
    layer has one, or both side by side for a parallel block. Returns (x,
    new_kv, new_state, aux)."""
    aux = {}
    if shard is not None:
        p = shard.materialize(p)  # OPT(fsdp): ZeRO weight gather
    inference = decode or kv is not None
    h = apply_norm(cfg, x, p.get("norm1"))
    h = maybe_bf16_grads(cfg, h)  # OPT(bf16_grads): bwd AR in 2-byte payloads
    new_kv = new_state = None
    if mixer == "attention":
        mix, new_kv = _attn_apply(cfg, h, p["attn"], positions, shard,
                                  kv=kv, decode=decode, comm=comm,
                                  start=start)
    elif decode:
        mix, new_state = mamba2_decode(cfg, h, p["ssm"], state, shard)
    else:
        mix, new_state = mamba2_forward(cfg, h, p["ssm"], shard, start=start)

    def mlp(h):
        if cfg.moe is not None:
            return moe_ffn(cfg, h, p["moe"], shard, inference=inference,
                           comm=comm)
        return gated_ffn(cfg, h, p["ffn"], shard, comm=comm), {}

    if cfg.parallel_block:
        ffn_out, aux = mlp(h)
        x = x + mix + ffn_out
    else:
        x = x + _scaled(cfg, mix)
        if "ffn" in p or "moe" in p:
            h2 = apply_norm(cfg, x, p.get("norm2"))
            h2 = maybe_bf16_grads(cfg, h2)
            ffn_out, aux = mlp(h2)
            x = x + _scaled(cfg, ffn_out)
    if shard is not None:
        x = shard.hidden(x)
    return x, new_kv, new_state, aux


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig, shard=None, comm=None):
        """``shard`` — GSPMD sharding-constraint helper (auto axes).
        ``comm`` — :class:`repro.serve.comm.ServeComm` for the manual-TP
        serve path: weights arrive Megatron-sharded via shard_map in_specs
        and every cross-rank exchange is an explicit collective on a
        per-purpose CommContext/VCI stream. Mutually exclusive."""
        assert shard is None or comm is None, "shard and comm are exclusive"
        self.cfg = cfg
        self.shard = shard
        self.comm = comm

    # -- embeddings ------------------------------------------------------
    def _tok_embed(self, emb, tok):
        """Token lookup; vocab-parallel (masked lookup + psum on the
        ``sample`` stream) when the table arrives row-sharded over TP."""
        if self.comm is not None and emb.shape[0] != self.cfg.vocab_size:
            v_loc = emb.shape[0]
            loc = tok - self.comm.rank() * v_loc
            ok = (loc >= 0) & (loc < v_loc)
            x = jnp.where(ok[..., None], emb[jnp.clip(loc, 0, v_loc - 1)], 0)
            return self.comm.psum(x, "sample")
        return emb[tok]

    def embed(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Returns (x: (B,S,d), positions: (B,S) or (S,))."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        tok = batch["tokens"]
        if cfg.modality == "audio":
            # tok: (B, K, S) — sum the K codebook embeddings
            emb = params["embed"]["tok"].astype(dtype)       # (K,V,d)
            x = jnp.sum(jax.vmap(lambda e, t: e[t], in_axes=(0, 1),
                                 out_axes=1)(emb, tok), axis=1)
            positions = jnp.arange(tok.shape[-1])
        elif cfg.modality == "vlm":
            emb = params["embed"]["tok"].astype(dtype)
            xt = emb[tok]                                     # (B,S_txt,d)
            img = batch["image_embeds"].astype(dtype)         # (B,P,1024)
            xi = img @ params["img_proj"]["w"].astype(dtype)
            x = jnp.concatenate([xi, xt], axis=1)
            positions = jnp.arange(x.shape[1])
        else:
            emb = params["embed"]["tok"].astype(dtype)
            x = self._tok_embed(emb, tok)
            positions = jnp.arange(tok.shape[-1])
        x = self._scale_embed(x)
        if self.shard is not None:
            x = self.shard.hidden(x)
        return x, positions

    def _scale_embed(self, x):
        m = self.cfg.embedding_multiplier
        return x if m == 1.0 else x * m

    def unembed(self, params, x) -> jax.Array:
        cfg = self.cfg
        x = apply_norm(cfg, x, params.get("final_norm"))
        if cfg.modality == "audio":
            w = params["lm_head"]["w"].astype(x.dtype)       # (K,d,V)
            logits = jnp.einsum("bsd,kdv->bksv", x, w)
        elif cfg.tie_embeddings:
            logits = x @ params["embed"]["tok"].astype(x.dtype).T
        else:
            logits = x @ params["lm_head"]["w"].astype(x.dtype)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if self.comm is not None and logits.shape[-1] != cfg.vocab_size:
            # vocab-parallel logits: gather shards on the sampling stream
            logits = self.comm.all_gather(logits, "sample",
                                          gather_axis=logits.ndim - 1)
        if self.shard is not None and cfg.modality != "audio":
            logits = self.shard.logits(logits)
        return logits

    # -- full-sequence forward (train / prefill) --------------------------
    def forward(self, params, batch, *, cache: Optional[DecodeCache] = None,
                start: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[DecodeCache]]:
        """Returns (logits, aux, new_cache). ``cache`` non-None => prefill
        into a fresh cache.

        ``start`` — (B,) int32 left-pad lengths for mixed-length prefill:
        row ``b``'s real tokens occupy positions ``[start[b], S)``; pad
        positions are masked out of attention, RoPE positions are shifted,
        and Mamba-2 layers give pad positions no step (``dt = 0``) and a
        zero conv input, so each row computes exactly what it would alone
        (models of one mixer per layer; zamba2's shared block has none).
        """
        cfg = self.cfg
        x, positions = self.embed(params, batch)
        if start is not None:
            if cfg.mixers is None:
                raise NotImplementedError(
                    "left-padded prefill needs one mixer per layer; zamba2's "
                    "shared attention block has no per-row starts")
            # per-row RoPE positions: the first real token sits at 0
            positions = jnp.maximum(positions[None, :] - start[:, None], 0)
        remat = cfg.remat != "none"

        if cfg.mixers is None:
            x, new_cache = self._zamba_stack(params, x, positions, cache,
                                             remat)
            aux: Dict[str, jax.Array] = {}
        elif self.comm is not None:
            # VCI streams chain ordering tokens across collectives; a token
            # updated inside a lax.scan body would leak its tracer, so the
            # comm-mode (inference) stack unrolls the layer loop.
            x, aux, new_cache = self._attn_stack_unrolled(
                params, x, positions, cache, start)
        else:
            x, aux, new_cache = self._layers(params, x, positions, cache,
                                             remat, decode=False, start=start)

        logits = self.unembed(params, x)
        return logits, aux, new_cache

    def _layers(self, params, x, positions, cache, remat, *, decode: bool,
                start=None):
        """The layer loop of every model of one mixer per layer, for
        training, prefill and decode: a scan over periods of the mixer
        pattern (``cfg.period``: one layer for dense, MoE and SSM models,
        ten for granite-4.0-h), the period's layers unrolled in its body.

        The caches ride in the carry beside ``x``: the stacked K/V (the
        paged pool, or the contiguous cache), which each attention layer
        writes at its index, and the stacked Mamba-2 state, which each
        Mamba-2 layer reads and writes at its index. So XLA keeps one buffer
        of each and updates it in place; nothing is mapped through the
        scan's inputs and outputs, which would copy the whole stack. The
        page table and write cursor are shared by every attention layer."""
        cfg = self.cfg
        per = cfg.period
        pattern = cfg.mixers[:per]
        n_per = cfg.num_layers // per
        # layers of each kind in a period; norms and MLPs are in every layer
        k_of = {"attn": pattern.count("attention"),
                "ssm": pattern.count("mamba")}

        def count(name):  # layers of a stack in each period
            return k_of.get(name, per)

        # stacks with one layer a period are scanned; the others are held
        # whole and indexed in place (a reshape into periods would copy a
        # stack whose device layout is not row-major)
        scan_layers = {n: t for n, t in params["layers"].items()
                       if count(n) == 1}
        held = {n: t for n, t in params["layers"].items() if count(n) > 1}
        kv = None if cache is None else cache.kv
        st = None if cache is None else cache.ssm
        paged = isinstance(kv, PagedKVCache)
        indexed = cache is not None or bool(held)

        def body(carry, scanned):
            x, kvs, sts = carry
            lp, p = scanned if indexed else (scanned, None)
            seen = {"attention": 0, "mamba": 0}
            auxes = []
            for j, mixer in enumerate(pattern):
                i = seen[mixer]
                seen[mixer] += 1
                own = "attn" if mixer == "attention" else "ssm"
                lj = {}
                for n in params["layers"]:
                    if n in k_of and n != own:
                        continue
                    c, idx = count(n), (i if n == own else j)
                    lj[n] = lp[n] if c == 1 else jax.tree_util.tree_map(
                        lambda a: a[p * c + idx], held[n])
                k = k_of[own]  # l: the layer's index in its kind's stack
                l = p if k == 1 or p is None else p * k + i
                if mixer == "attention":
                    view = None
                    if kv is not None:
                        kk, vv = kvs
                        view = (PagedKVLayer(kk, vv, kv.table, kv.length, l,
                                             kv.page_size) if paged
                                else KVCache(kk[l], vv[l], kv.length,
                                             kv.ring))
                    x, new_kv, _, aux = _block(
                        cfg, x, lj, positions, self.shard, kv=view,
                        decode=decode, comm=self.comm, start=start)
                    if kv is not None:
                        kvs = ((new_kv.k, new_kv.v) if paged
                               else (kk.at[l].set(new_kv.k),
                                     vv.at[l].set(new_kv.v)))
                else:
                    state = None
                    if decode:
                        state = SSMState(sts[0][l], sts[1][l])
                    x, _, new_st, aux = _block(
                        cfg, x, lj, positions, self.shard, mixer="mamba",
                        state=state, decode=decode, start=start)
                    if st is not None:
                        sts = (sts[0].at[l].set(
                                   new_st.conv.astype(sts[0].dtype)),
                               sts[1].at[l].set(new_st.ssd))
                auxes.append(jnp.stack([
                    aux.get("load_balance", jnp.zeros(())),
                    aux.get("router_z", jnp.zeros(()))]))
            return (x, kvs, sts), functools.reduce(operator.add, auxes)

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        kvs = () if kv is None else (kv.k, kv.v)
        sts = () if st is None else (st.conv, st.ssd)
        scanned = scan_layers
        if indexed:
            scanned = (scan_layers, jnp.arange(n_per, dtype=jnp.int32))
        (x, kvs, sts), aux_v = jax.lax.scan(body, (x, kvs, sts), scanned)
        new_cache = None
        if cache is not None:
            s_new = x.shape[1]
            new_kv = None
            if paged:
                new_kv = PagedKVCache(*kvs, kv.table, kv.length + s_new,
                                      kv.page_size)
            elif kv is not None:
                new_kv = KVCache(*kvs, kv.length + s_new, kv.ring)
            new_cache = DecodeCache(new_kv, None if st is None
                                    else SSMState(*sts),
                                    cache.length + s_new)
        aux = {"load_balance": aux_v[:, 0].sum(), "router_z": aux_v[:, 1].sum()}
        return x, aux, new_cache

    def _attn_stack_unrolled(self, params, x, positions, cache, start=None):
        """Python-loop layer stack for the comm (VCI-stream) serve path."""
        cfg = self.cfg
        take = jax.tree_util.tree_map
        kv = None if cache is None else cache.kv
        lb = rz = jnp.zeros(())
        for l in range(cfg.num_layers):
            lp = take(lambda a: a[l], params["layers"])
            x, new_kv, _, aux = _block(
                cfg, x, lp, positions, None,
                kv=None if kv is None else _layer_kv(kv, l), decode=False,
                comm=self.comm, start=start)
            if kv is not None:
                kv = _set_layer_kv(kv, l, new_kv)
            lb = lb + aux.get("load_balance", jnp.zeros(()))
            rz = rz + aux.get("router_z", jnp.zeros(()))
        new_cache = None
        if cache is not None:
            new_cache = DecodeCache(_advance_kv(kv, x.shape[1]), None,
                                    cache.length + x.shape[1])
        return x, {"load_balance": lb, "router_z": rz}, new_cache

    def _zamba_stack(self, params, x, positions, cache, remat):
        """zamba2: groups of ``hybrid_attn_every`` Mamba-2 blocks, each
        group followed by the shared attention block (its own KV cache per
        site), then the remaining blocks."""
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        L = cfg.num_layers

        def ssm_body(carry, scanned):
            x = carry
            lp = scanned[0] if cache is not None else scanned
            x, _, new_st, _ = _block(cfg, x, lp, positions, self.shard,
                                     mixer="mamba")
            return x, new_st

        if remat:
            ssm_body = jax.checkpoint(ssm_body, policy=_remat_policy(cfg))

        n_groups, rem = divmod(L, k)
        lp_all = params["layers"]
        take = jax.tree_util.tree_map
        lp_main = take(lambda a: a[: n_groups * k].reshape((n_groups, k) + a.shape[1:]),
                       lp_all)
        lp_rem = take(lambda a: a[n_groups * k:], lp_all)
        sa = params["shared_attn"]

        def attn_site(x, kv, decode=False):
            h = apply_norm(cfg, x, sa.get("norm1"))
            o, new_kv = _attn_apply(cfg, h, sa["attn"], positions, self.shard,
                                    kv=kv, decode=decode)
            x = x + o
            h2 = apply_norm(cfg, x, sa.get("norm2"))
            x = x + gated_ffn(cfg, h2, sa["ffn"], self.shard)
            return x, new_kv

        def group_body(carry, scanned):
            x = carry
            if cache is not None:
                (lps, sts, kvs) = scanned
                x, st_out = jax.lax.scan(ssm_body, x, (lps, sts))
                x, kv_out = attn_site(x, kvs)
                return x, (st_out, kv_out)
            lps = scanned
            x, _ = jax.lax.scan(ssm_body, x, lps)
            x, _ = attn_site(x, None)
            return x, None

        if remat:
            group_body = jax.checkpoint(group_body, policy=_remat_policy(cfg))

        if cache is not None:
            st_all = cache.ssm
            st_main = take(lambda a: a[: n_groups * k].reshape(
                (n_groups, k) + a.shape[1:]), st_all)
            st_rem = take(lambda a: a[n_groups * k:], st_all)
            kv_in = KVCache(cache.kv.k, cache.kv.v,
                            jnp.broadcast_to(cache.kv.length, (n_groups,)),
                            cache.kv.ring)
            x, (st_out, kv_out) = jax.lax.scan(
                group_body, x, (lp_main, st_main, kv_in))
            if rem:
                x, st_rem_out = jax.lax.scan(ssm_body, x, (lp_rem, st_rem))
                st_out = take(
                    lambda a, b: jnp.concatenate(
                        [a.reshape((n_groups * k,) + a.shape[2:]), b]),
                    st_out, st_rem_out)
            else:
                st_out = take(lambda a: a.reshape((n_groups * k,) + a.shape[2:]),
                              st_out)
            s_new = x.shape[1]
            new_cache = DecodeCache(
                KVCache(kv_out.k, kv_out.v, cache.kv.length + s_new, cache.kv.ring),
                st_out, cache.length + s_new)
            return x, new_cache

        x, _ = jax.lax.scan(group_body, x, lp_main)
        if rem:
            x, _ = jax.lax.scan(ssm_body, x, lp_rem)
        return x, None

    # -- one-token decode --------------------------------------------------
    def decode_step(self, params, tokens, cache: DecodeCache,
                    start: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, DecodeCache]:
        """tokens: (B,1) (or (B,K,1) audio). Returns (logits, new_cache).

        ``start`` — (B,) int32 per-row first-valid cache slot (the serve
        engine's left-pad/late-admission offset): cache reads mask slots
        below it and RoPE positions count from it.
        """
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if cfg.modality == "audio":
            emb = params["embed"]["tok"].astype(dtype)
            x = jnp.sum(jax.vmap(lambda e, t: e[t], in_axes=(0, 1),
                                 out_axes=1)(emb, tokens), axis=1)
        else:
            x = self._tok_embed(params["embed"]["tok"].astype(dtype), tokens)
        x = self._scale_embed(x)
        if start is not None:
            if cfg.mixers is None:
                raise NotImplementedError(
                    "per-row start offsets need one mixer per layer")
            positions = (cache.length - start)[:, None]
        else:
            positions = cache.length[None, None] + jnp.zeros(
                (x.shape[0], 1), jnp.int32)
        if self.shard is not None:
            x = self.shard.hidden(x)

        if cfg.mixers is None:
            x, new_cache = self._zamba_decode(params, x, positions, cache)
        elif self.comm is not None:
            x, new_cache = self._decode_unrolled(params, x, positions, cache,
                                                 start=start)
        else:
            x, _, new_cache = self._layers(params, x, positions, cache, False,
                                           decode=True, start=start)
        logits = self.unembed(params, x)
        return logits, new_cache

    def _decode_unrolled(self, params, x, positions, cache, start=None):
        """Python-loop decode for the comm path: see _attn_stack_unrolled."""
        cfg = self.cfg
        take = jax.tree_util.tree_map
        kv = cache.kv
        for l in range(cfg.num_layers):
            lp = take(lambda a: a[l], params["layers"])
            x, new_kv, _, _ = _block(cfg, x, lp, positions, None,
                                     kv=_layer_kv(kv, l), decode=True,
                                     comm=self.comm, start=start)
            kv = _set_layer_kv(kv, l, new_kv)
        return x, DecodeCache(_advance_kv(kv, 1), None, cache.length + 1)

    def _zamba_decode(self, params, x, positions, cache):
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        L = cfg.num_layers
        take = jax.tree_util.tree_map

        def ssm_body(carry, scanned):
            x = carry
            lp, st = scanned
            x, _, new_st, _ = _block(cfg, x, lp, positions, self.shard,
                                     mixer="mamba", state=st, decode=True)
            return x, new_st

        n_groups, rem = divmod(L, k)
        lp_all = params["layers"]
        lp_main = take(lambda a: a[: n_groups * k].reshape(
            (n_groups, k) + a.shape[1:]), lp_all)
        lp_rem = take(lambda a: a[n_groups * k:], lp_all)
        st_main = take(lambda a: a[: n_groups * k].reshape(
            (n_groups, k) + a.shape[1:]), cache.ssm)
        st_rem = take(lambda a: a[n_groups * k:], cache.ssm)
        sa = params["shared_attn"]

        def group_body(carry, scanned):
            x = carry
            lps, sts, kvs = scanned
            x, st_out = jax.lax.scan(ssm_body, x, (lps, sts))
            h = apply_norm(cfg, x, sa.get("norm1"))
            o, new_kv = _attn_apply(cfg, h, sa["attn"], positions, self.shard,
                                    kv=kvs, decode=True)
            x = x + o
            h2 = apply_norm(cfg, x, sa.get("norm2"))
            x = x + gated_ffn(cfg, h2, sa["ffn"], self.shard)
            return x, (st_out, new_kv)

        kv_in = KVCache(cache.kv.k, cache.kv.v,
                        jnp.broadcast_to(cache.kv.length, (n_groups,)),
                        cache.kv.ring)
        x, (st_out, kv_out) = jax.lax.scan(group_body, x, (lp_main, st_main, kv_in))
        st_out = take(lambda a: a.reshape((n_groups * k,) + a.shape[2:]), st_out)
        if rem:
            x, st_rem_out = jax.lax.scan(ssm_body, x, (lp_rem, st_rem))
            st_out = take(lambda a, b: jnp.concatenate([a, b]), st_out, st_rem_out)
        new_cache = DecodeCache(
            KVCache(kv_out.k, kv_out.v, cache.kv.length + 1, cache.kv.ring),
            st_out, cache.length + 1)
        return x, new_cache
