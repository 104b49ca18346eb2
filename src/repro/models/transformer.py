"""The unified model over all assigned architecture families.

One ``Model`` class covers: dense GQA/MQA transformers (gemma/yi/command-r/
olmo), MoE (mixtral/arctic), SSM (mamba2), hybrid SSM+shared-attention
(zamba2), VLM (phi-3-vision: stubbed patch embeddings spliced before text)
and audio (musicgen: 4 EnCodec codebook streams, summed embeddings, one LM
head per codebook).

Layers are stacked along a leading L axis and executed with ``lax.scan``
(compile-time control for 512-device dry-runs); hybrid archs scan groups of
``hybrid_attn_every`` SSM blocks followed by ONE shared-weight attention
block (zamba2's parameter-sharing trick — the weights are shared, but each
application site keeps its own KV cache).
"""

from __future__ import annotations

import math
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
    return {
        "in_proj": dense_init(ks[0], dims + (d, proj_out), dtype=dtype),
        "conv_w": 0.1 * jax.random.normal(ks[1], dims + (c.conv_width, d_in + d_bc),
                                          jnp.float32).astype(dtype),
        "A_log": a_init.astype(jnp.float32),
        "D": jnp.ones(dims + (nh,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "gate_norm": jnp.ones(dims + (d_in,), jnp.float32),
        "out_proj": dense_init(ks[0], dims + (d_in, d), dtype=dtype),
    }


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

    dims = (L,)
    if cfg.family in ("ssm", "hybrid"):
        layer = {"ssm": _ssm_params(cfg, keys[2], dims, dtype),
                 "norm1": _norm_params(cfg, dims)}
        if cfg.family == "hybrid":
            params["shared_attn"] = {
                "attn": _attn_params(cfg, keys[3], (), dtype),
                "ffn": _ffn_params(cfg, keys[4], (), dtype),
                "norm1": _norm_params(cfg, ()),
                "norm2": _norm_params(cfg, ()),
            }
    else:
        layer = {"attn": _attn_params(cfg, keys[2], dims, dtype),
                 "norm1": _norm_params(cfg, dims)}
        if cfg.moe is not None:
            layer["moe"] = _moe_params(cfg, keys[3], dims, dtype)
        else:
            layer["ffn"] = _ffn_params(cfg, keys[3], dims, dtype)
        if not cfg.parallel_block:
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
    """Per-arch decode state, layer-stacked along the leading axis."""

    kv: Optional[KVCache]       # (L|n_sites, B, S, KV, hd) stacked
    ssm: Optional[SSMState]     # (L, ...) stacked
    length: jax.Array           # () int32 — absolute tokens decoded


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> DecodeCache:
    if "kv_fp8" in cfg.opts and jnp.dtype(dtype) == jnp.bfloat16:
        # OPT(kv_fp8): fp8 KV storage — halves the decode memory-roofline
        # term (EXPERIMENTS §Perf); dequantized at attention read.
        dtype = jnp.float8_e4m3fn
    def stack(tree, n):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)

    kv = ssm = None
    if cfg.family in ("ssm", "hybrid"):
        ssm = stack(SSMState.init(cfg, batch, dtype=jnp.float32), cfg.num_layers)
        ssm = SSMState(ssm.conv.astype(dtype), ssm.ssd)
        if cfg.family == "hybrid":
            n_sites = cfg.num_layers // cfg.hybrid_attn_every
            kv0 = KVCache.init(cfg, batch, max_len, dtype)
            kv = KVCache(
                jnp.broadcast_to(kv0.k[None], (n_sites,) + kv0.k.shape),
                jnp.broadcast_to(kv0.v[None], (n_sites,) + kv0.v.shape),
                kv0.length, kv0.ring)
    else:
        kv0 = KVCache.init(cfg, batch, max_len, dtype)
        kv = KVCache(
            jnp.broadcast_to(kv0.k[None], (cfg.num_layers,) + kv0.k.shape),
            jnp.broadcast_to(kv0.v[None], (cfg.num_layers,) + kv0.v.shape),
            kv0.length, kv0.ring)
    return DecodeCache(kv, ssm, jnp.zeros((), jnp.int32))


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int, num_pages: int,
                     dtype=jnp.bfloat16) -> DecodeCache:
    """Paged decode cache: a fixed pool of ``num_pages`` pages of
    ``page_size`` tokens (page 0 reserved as trash) + an all-unmapped
    per-slot page table covering virtual positions ``[0, max_len)``.

    Attention-cache architectures only: ring (sliding-window) caches reuse
    slots modulo the window and SSM state has no per-position pages — the
    serve engine keeps the grouped contiguous fallback for those.
    """
    if cfg.family not in ("dense", "moe") or cfg.modality != "text":
        raise NotImplementedError(
            f"paged KV cache needs a text attention arch, got "
            f"family={cfg.family!r} modality={cfg.modality!r}")
    if cfg.sliding_window is not None and cfg.sliding_window < max_len:
        raise NotImplementedError(
            "paged KV cache does not support ring (sliding-window) caches; "
            "use the contiguous cache")
    if page_size < 1 or num_pages < 2:
        raise ValueError(f"need page_size >= 1 and num_pages >= 2 "
                         f"(page 0 is the trash page), got "
                         f"{page_size}/{num_pages}")
    if "kv_fp8" in cfg.opts and jnp.dtype(dtype) == jnp.bfloat16:
        dtype = jnp.float8_e4m3fn  # OPT(kv_fp8): see init_cache
    kvh = cfg.num_kv_heads * max(1, cfg.decode_kv_expand)
    max_pages = -(-max_len // page_size)
    shape = (cfg.num_layers, num_pages, page_size, kvh, cfg.head_dim)
    kv = PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                      jnp.full((batch, max_pages), -1, jnp.int32),
                      jnp.zeros((), jnp.int32), page_size)
    return DecodeCache(kv, None, jnp.zeros((), jnp.int32))


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


def _dense_block(cfg: ModelConfig, x, p, positions, shard,
                 kv=None, decode=False, comm=None, start=None):
    """Standard (or parallel) transformer block. Returns (x, new_kv, aux)."""
    aux = {}
    if shard is not None:
        p = shard.materialize(p)  # OPT(fsdp): ZeRO weight gather
    inference = decode or kv is not None
    h = apply_norm(cfg, x, p.get("norm1"))
    h = maybe_bf16_grads(cfg, h)  # OPT(bf16_grads): bwd AR in 2-byte payloads
    attn_out, new_kv = _attn_apply(cfg, h, p["attn"], positions, shard,
                                   kv=kv, decode=decode, comm=comm,
                                   start=start)
    if cfg.parallel_block:
        if cfg.moe is not None:
            ffn_out, aux = moe_ffn(cfg, h, p["moe"], shard,
                                   inference=inference, comm=comm)
        else:
            ffn_out = gated_ffn(cfg, h, p["ffn"], shard, comm=comm)
        x = x + attn_out + ffn_out
    else:
        x = x + attn_out
        h2 = apply_norm(cfg, x, p.get("norm2"))
        h2 = maybe_bf16_grads(cfg, h2)
        if cfg.moe is not None:
            ffn_out, aux = moe_ffn(cfg, h2, p["moe"], shard,
                                   inference=inference, comm=comm)
        else:
            ffn_out = gated_ffn(cfg, h2, p["ffn"], shard, comm=comm)
        x = x + ffn_out
    if shard is not None:
        x = shard.hidden(x)
    return x, new_kv, aux


def _ssm_block(cfg: ModelConfig, x, p, shard, state=None, decode=False):
    if shard is not None:
        p = shard.materialize(p)  # OPT(fsdp): ZeRO weight gather
    h = apply_norm(cfg, x, p.get("norm1"))
    if decode:
        out, new_state = mamba2_decode(cfg, h, p["ssm"], state, shard)
    else:
        out, new_state = mamba2_forward(cfg, h, p["ssm"], shard, initial=state)
    x = x + out
    if shard is not None:
        x = shard.hidden(x)
    return x, new_state


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
        if self.shard is not None:
            x = self.shard.hidden(x)
        return x, positions

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
        """Returns (logits, aux, new_cache). ``cache`` non-None => prefill.

        ``start`` — (B,) int32 left-pad lengths for mixed-length prefill:
        row ``b``'s real tokens occupy positions ``[start[b], S)``; pad
        positions are masked out of attention and RoPE positions are shifted
        so each row computes exactly what it would alone (attention archs
        only — SSM state offers no per-row mask).
        """
        cfg = self.cfg
        x, positions = self.embed(params, batch)
        if start is not None:
            if cfg.family in ("ssm", "hybrid"):
                raise NotImplementedError(
                    "left-padded prefill needs attention masking; SSM "
                    "recurrent state has no per-row pad mask")
            # per-row RoPE positions: the first real token sits at 0
            positions = jnp.maximum(positions[None, :] - start[:, None], 0)
        remat = cfg.remat != "none"

        if cfg.family in ("ssm", "hybrid"):
            x, new_cache = self._ssm_stack(params, x, positions, cache, remat)
            aux: Dict[str, jax.Array] = {}
        else:
            x, aux, new_cache = self._attn_stack(params, x, positions, cache,
                                                 remat, start=start)

        logits = self.unembed(params, x)
        return logits, aux, new_cache

    def _attn_stack(self, params, x, positions, cache, remat, start=None):
        cfg = self.cfg
        if self.comm is not None:
            # VCI streams chain ordering tokens across collectives; a token
            # updated inside a lax.scan body would leak its tracer, so the
            # comm-mode (inference) stack unrolls the layer loop.
            return self._attn_stack_unrolled(params, x, positions, cache,
                                             start)
        if cache is not None and isinstance(cache.kv, PagedKVCache):
            return self._attn_stack_paged(params, x, positions, cache, remat,
                                          decode=False, start=start)

        def body(carry, scanned):
            x = carry
            if cache is not None:
                lp, kv = scanned
            else:
                lp, kv = scanned, None
            x, new_kv, aux = _dense_block(cfg, x, lp, positions, self.shard,
                                          kv=kv, decode=False, comm=self.comm,
                                          start=start)
            aux_vec = jnp.stack([aux.get("load_balance", jnp.zeros(())),
                                 aux.get("router_z", jnp.zeros(()))])
            return x, (new_kv, aux_vec)

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        if cache is not None:
            kv_stack = KVCache(cache.kv.k, cache.kv.v,
                               jnp.broadcast_to(cache.kv.length, (cfg.num_layers,)),
                               cache.kv.ring)
            x, (kv_out, aux_v) = jax.lax.scan(body, x, (params["layers"], kv_stack))
            new_cache = DecodeCache(
                KVCache(kv_out.k, kv_out.v,
                        cache.kv.length + x.shape[1], cache.kv.ring),
                None, cache.length + x.shape[1])
        else:
            x, (_, aux_v) = jax.lax.scan(body, x, params["layers"])
            new_cache = None
        aux = {"load_balance": aux_v[:, 0].sum(), "router_z": aux_v[:, 1].sum()}
        return x, aux, new_cache

    def _attn_stack_paged(self, params, x, positions, cache, remat, *,
                          decode: bool, start=None):
        """The layer scan over the paged pool, for prefill and decode: the
        stacked K/V pools ride in the carry beside ``x``, each layer
        scatters its rows into them and the page gather reads them where
        they lie, so XLA keeps one pool buffer and updates it in place. The
        page table and write cursor are shared by every layer."""
        cfg = self.cfg
        pk = cache.kv

        def body(carry, scanned):
            x, k, v = carry
            lp, l = scanned
            layer = PagedKVLayer(k, v, pk.table, pk.length, l, pk.page_size)
            x, new_kv, aux = _dense_block(cfg, x, lp, positions, self.shard,
                                          kv=layer, decode=decode,
                                          comm=self.comm, start=start)
            aux_vec = jnp.stack([aux.get("load_balance", jnp.zeros(())),
                                 aux.get("router_z", jnp.zeros(()))])
            return (x, new_kv.k, new_kv.v), aux_vec

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, k, v), aux_v = jax.lax.scan(body, (x, pk.k, pk.v),
                                        (params["layers"], layers))
        s_new = x.shape[1]
        new_cache = DecodeCache(
            PagedKVCache(k, v, pk.table, pk.length + s_new, pk.page_size),
            None, cache.length + s_new)
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
            x, new_kv, aux = _dense_block(
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

    def _ssm_stack(self, params, x, positions, cache, remat):
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        L = cfg.num_layers

        def ssm_body(carry, scanned):
            x = carry
            if cache is not None:
                lp, st = scanned
            else:
                lp, st = scanned, None
            x, new_st = _ssm_block(cfg, x, lp, self.shard, state=st, decode=False)
            return x, new_st

        if remat:
            ssm_body = jax.checkpoint(ssm_body, policy=_remat_policy(cfg))

        if cfg.family == "ssm":
            if cache is not None:
                x, st_out = jax.lax.scan(ssm_body, x, (params["layers"], cache.ssm))
                return x, DecodeCache(None, st_out, cache.length + x.shape[1])
            x, _ = jax.lax.scan(ssm_body, x, params["layers"])
            return x, None

        # ---- hybrid: groups of k ssm blocks + shared attention --------------
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
        if start is not None:
            if cfg.family in ("ssm", "hybrid"):
                raise NotImplementedError(
                    "per-row start offsets need attention masking")
            positions = (cache.length - start)[:, None]
        else:
            positions = cache.length[None, None] + jnp.zeros(
                (x.shape[0], 1), jnp.int32)
        if self.shard is not None:
            x = self.shard.hidden(x)

        if cfg.family in ("ssm", "hybrid"):
            x, new_cache = self._decode_ssm(params, x, positions, cache)
        else:
            x, new_cache = self._decode_attn(params, x, positions, cache,
                                             start=start)
        logits = self.unembed(params, x)
        return logits, new_cache

    def _decode_attn(self, params, x, positions, cache, start=None):
        cfg = self.cfg
        if self.comm is not None:  # unrolled: see _attn_stack_unrolled
            take = jax.tree_util.tree_map
            kv = cache.kv
            for l in range(cfg.num_layers):
                lp = take(lambda a: a[l], params["layers"])
                x, new_kv, _ = _dense_block(cfg, x, lp, positions, None,
                                            kv=_layer_kv(kv, l), decode=True,
                                            comm=self.comm, start=start)
                kv = _set_layer_kv(kv, l, new_kv)
            new_cache = DecodeCache(_advance_kv(kv, 1), None,
                                    cache.length + 1)
            return x, new_cache

        if isinstance(cache.kv, PagedKVCache):
            x, _, new_cache = self._attn_stack_paged(
                params, x, positions, cache, False, decode=True, start=start)
            return x, new_cache

        def body(carry, scanned):
            x = carry
            lp, kv = scanned
            x, new_kv, _ = _dense_block(cfg, x, lp, positions, self.shard,
                                        kv=kv, decode=True, comm=self.comm,
                                        start=start)
            return x, new_kv

        kv_stack = KVCache(cache.kv.k, cache.kv.v,
                           jnp.broadcast_to(cache.kv.length, (cfg.num_layers,)),
                           cache.kv.ring)
        x, kv_out = jax.lax.scan(body, x, (params["layers"], kv_stack))
        new_cache = DecodeCache(
            KVCache(kv_out.k, kv_out.v, cache.kv.length + 1, cache.kv.ring),
            None, cache.length + 1)
        return x, new_cache

    def _decode_ssm(self, params, x, positions, cache):
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        L = cfg.num_layers
        take = jax.tree_util.tree_map

        def ssm_body(carry, scanned):
            x = carry
            lp, st = scanned
            x, new_st = _ssm_block(cfg, x, lp, self.shard, state=st, decode=True)
            return x, new_st

        if cfg.family == "ssm":
            x, st_out = jax.lax.scan(ssm_body, x, (params["layers"], cache.ssm))
            return x, DecodeCache(None, st_out, cache.length + 1)

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
