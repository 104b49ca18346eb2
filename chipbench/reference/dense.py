"""Plain float32 reference of a dense decoder (OLMo-style), written from the
published description (arXiv:2402.00838): pre-norm blocks of causal
multi-head attention with rotary positions and a SwiGLU feed-forward, a
final norm and a head tied to the embedding (or not, as configured).

Departure, noted: rotary embeddings rotate the interleaved pairs of each
head's dimensions, ``(2i, 2i+1)``, where the published model rotates
``(i, i + head_dim/2)``. The two are the same map up to a fixed permutation
of the query and key columns, which a checkpoint converter applies; the
benchmark's weights are random, so the convention is only a layout.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (F32, einsum, layer_norm, mm,
                                        normal, rms_norm, token_ce)


def _sizes(cfg):
    return (cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
            cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
            cfg["vocab_size"])


def init_params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """The benchmark's weights in the program's layout: layers stacked on a
    leading axis, matrices in the served dtype, norm scales in float32."""
    L, d, h, kvh, hd, ff, v = _sizes(cfg)
    if cfg.get("use_bias"):
        raise NotImplementedError("biases are not in this reference")
    dt = jnp.dtype(cfg["param_dtype"])
    ks = jax.random.split(key, 9)
    params: Dict[str, Any] = {"embed": {"tok": normal(ks[0], (v, d), 0.02,
                                                       dt)}}
    layers = {
        "attn": {"wq": normal(ks[1], (L, d, h * hd), d ** -0.5, dt),
                 "wk": normal(ks[2], (L, d, kvh * hd), d ** -0.5, dt),
                 "wv": normal(ks[3], (L, d, kvh * hd), d ** -0.5, dt),
                 "wo": normal(ks[4], (L, h * hd, d), (h * hd) ** -0.5, dt)},
        "ffn": {"w_gate": normal(ks[5], (L, d, ff), d ** -0.5, dt),
                "w_up": normal(ks[6], (L, d, ff), d ** -0.5, dt),
                "w_down": normal(ks[7], (L, ff, d), ff ** -0.5, dt)},
    }
    if cfg["norm"] != "nonparametric":
        layers["norm1"] = {"scale": jnp.ones((L, d), F32)}
        layers["norm2"] = {"scale": jnp.ones((L, d), F32)}
        params["final_norm"] = {"scale": jnp.ones((d,), F32)}
    params["layers"] = layers
    if not cfg["tie_embeddings"]:
        params["lm_head"] = {"w": normal(ks[8], (d, v), d ** -0.5, dt)}
    return params


def _norm(cfg, x, p):
    if cfg["norm"] == "nonparametric":
        return layer_norm(x, cfg.get("norm_eps", 1e-5))
    if cfg["norm"] == "layernorm":
        return layer_norm(x, cfg.get("norm_eps", 1e-5)) * p["scale"]
    return rms_norm(x, cfg.get("norm_eps", 1e-6)) * p["scale"]


def _rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1; interleaved pairs (see above)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _block(cfg, x, p, control):
    b, s, _ = x.shape
    h, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    a = p["attn"]
    y = _norm(cfg, x, p.get("norm1"))
    q = _rope(mm(y, a["wq"], control).reshape(b, s, h, hd),
              cfg["rope_theta"])
    k = _rope(mm(y, a["wk"], control).reshape(b, s, kvh, hd),
              cfg["rope_theta"])
    v = mm(y, a["wv"], control).reshape(b, s, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, control) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = einsum("bhqk,bkhd->bqhd", probs, v, control).reshape(b, s, h * hd)
    x = x + mm(o, a["wo"], control)
    f = p["ffn"]
    y = _norm(cfg, x, p.get("norm2"))
    act = jax.nn.silu if cfg["hidden_act"] == "silu" else (
        lambda t: jax.nn.gelu(t, approximate=True))
    g = act(mm(y, f["w_gate"], control)) * mm(y, f["w_up"], control)
    return x + mm(g, f["w_down"], control)


def hidden(params, tokens, cfg, control: bool = False):
    """Final hidden states (B, S, d), float32; layer by layer, each layer
    recomputed in the backward pass so that a long batch fits."""
    x = params["embed"]["tok"].astype(F32)[tokens]

    @jax.checkpoint
    def body(x, p):
        return _block(cfg, x, p, control), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _norm(cfg, x, params.get("final_norm"))


def unembed(params, x, cfg, control: bool = False):
    w = (params["embed"]["tok"].T if cfg["tie_embeddings"]
         else params["lm_head"]["w"])
    return mm(x, w, control)


def forward(params, tokens, cfg, control: bool = False):
    """Logits (B, S, V) of a causal forward from position 0, float32."""
    with jax.default_matmul_precision("highest"):
        return unembed(params, hidden(params, tokens, cfg, control), cfg,
                       control)


def loss_sum(params, tokens, labels, cfg, control: bool = False):
    """Summed next-token cross entropy of a batch."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, cfg, control)
        return jnp.sum(token_ce(unembed(params, x, cfg, control), labels))
