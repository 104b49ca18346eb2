"""Operations and bytes that the work needs, from the configuration's sizes:
the yardstick the roofline and utilisation metrics divide by.

A matrix product of an ``n``-vector with an ``n x m`` matrix counts
``2 n m`` operations. Elementwise work, norms and softmax are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

BYTES = {"bfloat16": 2, "float32": 4}


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that each token multiplies once in a forward pass: every
    layer's projections and the output head (the embedding lookup is not a
    product; a tied head counts the shared table once)."""
    d, L, v = cfg["d_model"], cfg["num_layers"], cfg["vocab_size"]
    if cfg.get("ssm"):
        s = cfg["ssm"]
        d_in = s["expand"] * d
        nh = d_in // s["head_dim"]
        d_bc = 2 * s["ngroups"] * s["d_state"]
        layer = (d * (2 * d_in + d_bc + nh) + d_in * d
                 + s["conv_width"] * (d_in + d_bc))
    else:
        q = cfg["num_heads"] * cfg["head_dim"]
        kv = cfg["num_kv_heads"] * cfg["head_dim"]
        layer = d * (q + 2 * kv) + q * d + 3 * d * cfg["d_ff"]
    return L * layer + v * d


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of the served weights (the embedding table once)."""
    return matmul_params(cfg) * BYTES[cfg["param_dtype"]]


def attention_flops(cfg: Dict[str, Any], context: float) -> float:
    """Forward operations of one token's attention over ``context`` keys:
    scores and the weighted sum, ``4 L H hd context``."""
    if cfg.get("ssm"):
        return 0.0
    return 4.0 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"] \
        * context


def ssd_flops(cfg: Dict[str, Any]) -> float:
    """Forward operations per token of the chunked state-space algorithm of
    arXiv:2405.21060 (section 6), chunk length Q, state N, head size P, G
    groups and H heads: ``C B^T`` within the chunk (``2 Q N`` per group),
    its product with the inputs (``2 Q P`` per head), the chunk's state
    (``2 N P`` per head) and the output read from the carried state
    (``2 N P`` per head)."""
    s = cfg.get("ssm")
    if not s:
        return 0.0
    q, n, p, g = s["chunk_size"], s["d_state"], s["head_dim"], s["ngroups"]
    h = s["expand"] * cfg["d_model"] // p
    return cfg["num_layers"] * (2.0 * q * n * g + h * (2.0 * q * p
                                                       + 4.0 * n * p))


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Model operations per trained token, forward and backward:
    ``6 N`` plus attention as in PaLM (arXiv:2204.02311, appendix B),
    ``12 L H hd T``, plus three times the forward state-space work.
    Recomputation is not counted."""
    att = 0.0
    if not cfg.get("ssm"):
        att = 12.0 * cfg["num_layers"] * cfg["num_heads"] \
            * cfg["head_dim"] * seq_len
    return 6.0 * matmul_params(cfg) + att + 3.0 * ssd_flops(cfg)


def serve_flops(cfg: Dict[str, Any],
                requests: Iterable[Tuple[int, int]]) -> float:
    """Forward operations of serving requests of (prompt length, served
    tokens): every prompt token and every served token fed back (all but
    the last) passes ``2 N`` of products and attends to the tokens before
    it, itself included."""
    n2 = 2.0 * matmul_params(cfg)
    total = 0.0
    for plen, out in requests:
        fed = plen + max(out - 1, 0)
        # contexts 1 .. fed: sum = fed (fed + 1) / 2
        total += n2 * fed + attention_flops(cfg, fed * (fed + 1) / 2.0)
    return total


def kv_token_bytes(cfg: Dict[str, Any], dtype: str = "bfloat16") -> int:
    """Bytes of one token's keys and values over all layers."""
    return (2 * cfg["num_layers"] * cfg["num_kv_heads"] * cfg["head_dim"]
            * BYTES[dtype])


def decode_least_bytes(cfg: Dict[str, Any],
                       requests: Iterable[Tuple[int, int]]) -> float:
    """The least bytes the decode steps of these requests read, without the
    weights: each served token fed back reads the keys and values of the
    tokens before it and itself, at the model's bf16 dtype."""
    per = kv_token_bytes(cfg)
    total = 0.0
    for plen, out in requests:
        # decode inputs k = 1 .. out-1 attend plen + k tokens
        m = max(out - 1, 0)
        total += per * (m * plen + m * (m + 1) / 2.0)
    return total


def gather_page_bytes(cfg: Dict[str, Any], page_size: int,
                      cache_dtype: str) -> int:
    """Bytes of one layer's K (or V) page of the pool."""
    return page_size * cfg["num_kv_heads"] * cfg["head_dim"] \
        * BYTES[cache_dtype]


def paged_gather_least_bytes(cfg: Dict[str, Any], batch: int, max_len: int,
                             page_size: int, cache_dtype: str,
                             requests: Iterable[Tuple[int, int]],
                             calls: int, steps: int) -> float:
    """The least bytes ``calls`` page gathers over ``steps`` decode steps
    must move: each writes the whole ``batch x pages`` view, and reads at
    least the pages that hold each live request's tokens (a request's
    decode input k sits on ``ceil((prompt + k) / page)`` pages); unmapped
    entries need no read."""
    page = gather_page_bytes(cfg, page_size, cache_dtype)
    view = batch * -(-max_len // page_size) * page
    live = sum(-(-(plen + k) // page_size)
               for plen, out in requests for k in range(1, out))
    return calls * view + (calls / steps) * live * page
