"""Operations and bytes that the work needs, counted layer by layer from a
configuration's ``layer_types``: attention layers, Mamba-2 layers, or both
in one model, with an MLP wherever ``d_ff`` is set. A configuration without
``layer_types`` is all attention, or all Mamba-2 where it has ``ssm``, and
gets ``ops.py``'s numbers.

As in ``ops.py``, a matrix product of an ``n``-vector with an ``n x m``
matrix counts ``2 n m`` operations; elementwise work, norms and softmax are
not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Tuple

BYTES = {"bfloat16": 2, "float32": 4}


def layer_types(cfg: Dict[str, Any]) -> List[str]:
    if cfg.get("layer_types"):
        return list(cfg["layer_types"])
    return ["mamba" if cfg.get("ssm") else "attention"] * cfg["num_layers"]


def counts(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(attention layers, Mamba-2 layers)."""
    t = layer_types(cfg)
    return t.count("attention"), t.count("mamba")


def _ssm_dims(cfg):
    s = cfg["ssm"]
    d_in = s["expand"] * cfg["d_model"]
    return s, d_in, d_in // s["head_dim"], 2 * s["ngroups"] * s["d_state"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that each token multiplies once in a forward pass: each
    layer's mixer (attention projections, or the Mamba-2 in- and
    out-projections and conv), each layer's MLP, and the output head (a tied
    head counts the shared table once)."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    n_attn, n_mamba = counts(cfg)
    total = v * d + cfg["num_layers"] * 3 * d * cfg.get("d_ff", 0)
    if n_attn:
        q = cfg["num_heads"] * cfg["head_dim"]
        kv = cfg["num_kv_heads"] * cfg["head_dim"]
        total += n_attn * (d * (q + 2 * kv) + q * d)
    if n_mamba:
        s, d_in, nh, d_bc = _ssm_dims(cfg)
        total += n_mamba * (d * (2 * d_in + d_bc + nh) + d_in * d
                            + s["conv_width"] * (d_in + d_bc))
    return total


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of the served weights (the embedding table once)."""
    return matmul_params(cfg) * BYTES[cfg["param_dtype"]]


def attention_flops(cfg: Dict[str, Any], context: float) -> float:
    """Forward operations of one token's attention over ``context`` keys,
    on the attention layers: ``4 n_attn H hd context``."""
    n_attn = counts(cfg)[0]
    if not n_attn:
        return 0.0
    return 4.0 * n_attn * cfg["num_heads"] * cfg["head_dim"] * context


def ssd_flops(cfg: Dict[str, Any]) -> float:
    """Forward operations per token of the chunked state-space algorithm on
    the Mamba-2 layers (``ops.ssd_flops``'s count per layer)."""
    n_mamba = counts(cfg)[1]
    if not n_mamba:
        return 0.0
    s, _, h, _ = _ssm_dims(cfg)
    q, n, p, g = s["chunk_size"], s["d_state"], s["head_dim"], s["ngroups"]
    return n_mamba * (2.0 * q * n * g + h * (2.0 * q * p + 4.0 * n * p))


def recurrence_flops(cfg: Dict[str, Any]) -> float:
    """The least forward operations per token of the Mamba-2 layers' state
    map: the state's update (``2 N P`` a head) and its read (``2 N P``)."""
    n_mamba = counts(cfg)[1]
    if not n_mamba:
        return 0.0
    s, _, h, _ = _ssm_dims(cfg)
    return n_mamba * h * 4.0 * s["d_state"] * s["head_dim"]


def serve_flops(cfg: Dict[str, Any],
                requests: Iterable[Tuple[int, int]]) -> float:
    """Forward operations of serving requests of (prompt length, served
    tokens): every prompt token and every served token fed back (all but
    the last) passes ``2 N`` of products, attends on the attention layers to
    the tokens before it, itself included, and takes the state map's least
    operations on the Mamba-2 layers."""
    n2 = 2.0 * matmul_params(cfg)
    rec = recurrence_flops(cfg)
    total = 0.0
    for plen, out in requests:
        fed = plen + max(out - 1, 0)
        total += ((n2 + rec) * fed
                  + attention_flops(cfg, fed * (fed + 1) / 2.0))
    return total


def kv_token_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of one token's keys and values over the attention layers."""
    n_attn = counts(cfg)[0]
    if not n_attn:
        return 0
    return (2 * n_attn * cfg["num_kv_heads"] * cfg["head_dim"]
            * BYTES[dtype])


def state_bytes(cfg: Dict[str, Any], cache_dtype: str) -> int:
    """Bytes of one slot's recurrent state over the Mamba-2 layers, at the
    dtypes the cache stores: the SSD state ``(H, N, P)`` in float32 and the
    conv window ``(conv_width - 1, d_inner + 2 G N)`` in the cache dtype."""
    n_mamba = counts(cfg)[1]
    if not n_mamba:
        return 0
    s, d_in, h, d_bc = _ssm_dims(cfg)
    ssd = h * s["d_state"] * s["head_dim"] * BYTES["float32"]
    conv = (s["conv_width"] - 1) * (d_in + d_bc) * BYTES[cache_dtype]
    return n_mamba * (ssd + conv)


def decode_least_bytes(cfg: Dict[str, Any],
                       requests: Iterable[Tuple[int, int]]) -> float:
    """The least bytes the decode steps of these requests read, without the
    weights and the recurrent state: each served token fed back reads the
    keys and values of the tokens before it and itself on the attention
    layers, at the model's bf16 dtype."""
    per = kv_token_bytes(cfg)
    total = 0.0
    for plen, out in requests:
        m = max(out - 1, 0)
        total += per * (m * plen + m * (m + 1) / 2.0)
    return total


def decode_state_bytes(cfg: Dict[str, Any], cache_dtype: str,
                       requests: Iterable[Tuple[int, int]]) -> float:
    """Each served token fed back reads its slot's recurrent state and
    writes it back."""
    fed = sum(max(out - 1, 0) for _, out in requests)
    return 2.0 * state_bytes(cfg, cache_dtype) * fed
