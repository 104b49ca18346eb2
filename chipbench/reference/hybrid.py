"""Plain float32 reference of a hybrid of Mamba-2 and attention layers with a
per-layer pattern (IBM Granite 4.0-H, Hugging Face ``granitemoehybrid``
without experts), written from the published layer equations of
``GraniteMoeHybridDecoderLayer``:

    x_0 = embedding_multiplier * embed(tokens)
    h   = x + residual_multiplier * mixer(rms(x))      (mixer by layer_types)
    x'  = h + residual_multiplier * mlp(rms(h))         (in every layer)
    logits = rms(x_L) @ embed^T / logits_scaling        (tied head)

``mixer`` is either grouped-query causal attention with no position
embedding (NoPE) and scores scaled by ``attention_multiplier``, or the
Mamba-2 mixer of ``reference/ssm.py`` with a bias on its depthwise
convolution. ``mlp`` is SwiGLU.

The state-space map is written in its quadratic form over the whole
sequence, ``y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r = s+1}^{t} dt_r A)
dt_s x_s``, exponentiating only where ``s <= t``; a sequence is processed
in blocks of query positions, and a batch one sequence at a time, so that a
long sequence fits beside the weights.

Departures from the Hugging Face model, noted:

* the MLP's fused ``input_linear`` (gate and up projections stacked) is
  kept as two matrices, ``w_gate`` and ``w_up``: a layout;
* the gated RMSNorm normalises over all of ``d_inner``, which is the
  published model's group size at ``mamba_n_groups`` 1, the only value
  this reference accepts;
* the weights are random, from the seed, with this benchmark's own
  initialisation, not the published checkpoint's (the embedding table at
  ``0.02 / embedding_multiplier``, see ``init_params``);
* everything is float32 at the highest matrix precision, where the
  published model computes in bfloat16.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (F32, einsum, mm, normal, rms_norm,
                                        token_ce)

T_BLOCK = 512  # query positions per block of the state-space map


def _dims(cfg):
    s = cfg["ssm"]
    d = cfg["d_model"]
    d_in = s["expand"] * d
    nh = d_in // s["head_dim"]
    d_bc = 2 * s["ngroups"] * s["d_state"]
    if s["ngroups"] != 1:
        raise NotImplementedError("the gated norm here has one group")
    return d, d_in, nh, d_bc, s


def _counts(cfg):
    types = list(cfg["layer_types"])
    return types, types.count("attention"), types.count("mamba")


def init_params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """The benchmark's weights in the program's layout: one stack per kind
    of weight (attention over the attention layers, Mamba-2 over the Mamba-2
    layers, norms and MLPs over every layer), matrices in the served dtype,
    norm scales and the state-space parameters in float32. A and dt follow
    Mamba-2's initialisation: A in [1, 16], dt log-uniform in [1e-3, 1e-1]."""
    L, v, ff = cfg["num_layers"], cfg["vocab_size"], cfg["d_ff"]
    h, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    d, d_in, nh, d_bc, s = _dims(cfg)
    _, na, nm = _counts(cfg)
    dt_ = jnp.dtype(cfg["param_dtype"])
    ks = jax.random.split(key, 16)
    dt0 = jnp.exp(jax.random.uniform(ks[10], (nm, nh), F32)
                  * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    ch = d_in + d_bc
    # the table's rows at 0.02 / embedding_multiplier, so that the
    # multiplier brings each looked-up row to 0.02: at 0.02 itself the
    # embedding of the current token outweighs the 80 residual branches in
    # the tied head, and greedy decoding only repeats the token
    emb = 0.02 / cfg["embedding_multiplier"]
    return {
        "embed": {"tok": normal(ks[0], (v, d), emb, dt_)},
        "layers": {
            "norm1": {"scale": jnp.ones((L, d), F32)},
            "norm2": {"scale": jnp.ones((L, d), F32)},
            "attn": {"wq": normal(ks[1], (na, d, h * hd), d ** -0.5, dt_),
                     "wk": normal(ks[2], (na, d, kvh * hd), d ** -0.5, dt_),
                     "wv": normal(ks[3], (na, d, kvh * hd), d ** -0.5, dt_),
                     "wo": normal(ks[4], (na, h * hd, d), (h * hd) ** -0.5,
                                  dt_)},
            "ssm": {
                "in_proj": normal(ks[5], (nm, d, 2 * d_in + d_bc + nh),
                                  d ** -0.5, dt_),
                "conv_w": normal(ks[6], (nm, s["conv_width"], ch), 0.1, dt_),
                "conv_b": normal(ks[7], (nm, ch), 0.1, dt_),
                "A_log": jnp.log(jax.random.uniform(ks[11], (nm, nh), F32,
                                                    1.0, 16.0)),
                "D": jnp.ones((nm, nh), F32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "gate_norm": jnp.ones((nm, d_in), F32),
                "out_proj": normal(ks[8], (nm, d_in, d), d_in ** -0.5, dt_),
            },
            "ffn": {"w_gate": normal(ks[12], (L, d, ff), d ** -0.5, dt_),
                    "w_up": normal(ks[13], (L, d, ff), d ** -0.5, dt_),
                    "w_down": normal(ks[14], (L, ff, d), ff ** -0.5, dt_)},
        },
        "final_norm": {"scale": jnp.ones((d,), F32)},
    }


def _ssd(x, dt, A, B, C, control):
    """One sequence. x: (S, H, P); dt: (S, H); A: (H,); B, C: (S, G, N).
    Query positions in blocks of ``T_BLOCK``."""
    s, h, _ = x.shape
    g = B.shape[1]
    cum = jnp.cumsum(dt * A, axis=0)                          # (S, H)
    t = min(T_BLOCK, s)
    pad = -s % t
    cum_q = jnp.pad(cum, ((0, pad), (0, 0)))
    C_q = jnp.pad(C, ((0, pad), (0, 0), (0, 0)))
    src = jnp.arange(s)

    def block(i):
        rows = i * t + jnp.arange(t)                          # query t
        cq = jax.lax.dynamic_slice_in_dim(cum_q, i * t, t)    # (t, H)
        Cq = jax.lax.dynamic_slice_in_dim(C_q, i * t, t)      # (t, G, N)
        lower = (src[None, :] <= rows[:, None])[:, :, None]  # (t, S, 1)
        diff = cq[:, None, :] - cum[None, :, :]               # (t, S, H)
        decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
        cb = einsum("tgn,sgn->gts", Cq, B, control)           # (G, t, S)
        cb = jnp.repeat(cb, h // g, axis=0)                   # (H, t, S)
        w = jnp.transpose(cb, (1, 2, 0)) * decay * dt[None, :, :]
        return einsum("tsh,shp->thp", w, x, control)          # (t, H, P)

    y = jax.lax.map(block, jnp.arange((s + pad) // t))
    return y.reshape((-1,) + y.shape[2:])[:s]


def _conv(xbc, w, b):
    """Depthwise causal convolution of one sequence, with its bias.
    xbc: (S, ch)."""
    k = w.shape[0]
    xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    return sum(xp[i: i + xbc.shape[0]] * w[i] for i in range(k)) + b


def _mamba(cfg, y, q, control):
    """The Mamba-2 mixer over one sequence y: (S, d), normed."""
    d, d_in, nh, d_bc, s = _dims(cfg)
    eps = cfg["norm_eps"]
    zxbcdt = mm(y, q["in_proj"], control)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + d_bc], -1)
    xbc = jax.nn.silu(_conv(xbc, q["conv_w"].astype(F32),
                            q["conv_b"].astype(F32)))
    xs, B, C = jnp.split(xbc, [d_in, d_in + d_bc // 2], -1)
    n = s["d_state"]
    xs = xs.reshape(-1, nh, s["head_dim"])
    B = B.reshape(-1, s["ngroups"], n)
    C = C.reshape(-1, s["ngroups"], n)
    dt = jax.nn.softplus(dt + q["dt_bias"])
    A = -jnp.exp(q["A_log"])
    out = _ssd(xs, dt, A, B, C, control) + xs * q["D"][None, :, None]
    out = out.reshape(-1, d_in) * jax.nn.silu(z)
    out = rms_norm(out, eps) * q["gate_norm"]
    return mm(out, q["out_proj"], control)


def _attention(cfg, y, a, control):
    """Causal grouped-query attention over one sequence y: (S, d), normed;
    no position embedding, scores scaled by ``attention_multiplier``."""
    s = y.shape[0]
    h, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = mm(y, a["wq"], control).reshape(s, h, hd)
    k = jnp.repeat(mm(y, a["wk"], control).reshape(s, kvh, hd), h // kvh, 1)
    v = jnp.repeat(mm(y, a["wv"], control).reshape(s, kvh, hd), h // kvh, 1)
    scores = einsum("qhd,khd->hqk", q, k, control) \
        * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = einsum("hqk,khd->qhd", probs, v, control).reshape(s, h * hd)
    return mm(o, a["wo"], control)


def _mlp(y, f, control):
    g = jax.nn.silu(mm(y, f["w_gate"], control)) * mm(y, f["w_up"], control)
    return mm(g, f["w_down"], control)


def _period(types):
    """The shortest run of ``layer_types`` that repeats to make them all."""
    n = len(types)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and types == types[:p] * (n // p))


def _sequence(params, tokens, cfg, control):
    """Final hidden states (S, d) of one sequence, float32: a scan over
    periods of ``layer_types``, each period's layers in order."""
    eps, rm = cfg["norm_eps"], cfg["residual_multiplier"]
    types = _counts(cfg)[0]
    per = _period(types)
    n = len(types) // per
    pattern = types[:per]

    def periods(tree):  # (count, ...) -> (n, count / n, ...)
        return jax.tree_util.tree_map(
            lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), tree)

    def body(x, lay):
        seen = {"attention": 0, "mamba": 0}
        for j, kind in enumerate(pattern):
            i = seen[kind]
            seen[kind] += 1
            own = "attn" if kind == "attention" else "ssm"
            mine = jax.tree_util.tree_map(lambda a: a[i], lay[own])
            y = rms_norm(x, eps) * lay["norm1"]["scale"][j]
            if kind == "attention":
                mix = _attention(cfg, y, mine, control)
            else:
                mix = _mamba(cfg, y, mine, control)
            x = x + rm * mix
            y = rms_norm(x, eps) * lay["norm2"]["scale"][j]
            f = jax.tree_util.tree_map(lambda a: a[j], lay["ffn"])
            x = x + rm * _mlp(y, f, control)
        return x, None

    x = params["embed"]["tok"].astype(F32)[tokens] \
        * cfg["embedding_multiplier"]
    x, _ = jax.lax.scan(body, x, periods(params["layers"]))
    return rms_norm(x, eps) * params["final_norm"]["scale"]


def hidden(params, tokens, cfg, control: bool = False):
    """Final hidden states (B, S, d), float32, one sequence at a time."""
    return jax.lax.map(lambda t: _sequence(params, t, cfg, control), tokens)


def unembed(params, x, cfg, control: bool = False):
    return mm(x, params["embed"]["tok"].T, control) / cfg["logits_scaling"]


def forward(params, tokens, cfg, control: bool = False):
    """Logits (B, S, V) of a causal forward from position 0, float32, one
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda t: unembed(params, _sequence(params, t, cfg, control),
                              cfg, control), tokens)


def loss_sum(params, tokens, labels, cfg, control: bool = False):
    """Summed next-token cross entropy of a batch."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, tokens, cfg, control)
        return jnp.sum(token_ce(logits, labels))
