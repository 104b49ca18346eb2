"""The one traffic generator. A mix is a data file under ``traffic/``; its
``kind`` says which of the two shapes of work it describes.

``closed_calls`` (serving): the window is a run of ``ServeEngine.generate``
calls, each holding ``requests_per_call`` requests offered at once. Every
call holds the same multiset of sizes: prompt lengths in the stated
proportions (largest remainder) and output lengths at evenly spaced
quantiles of their distribution. The call's index alone chooses their order
and the pairing of prompt with output length; the seed chooses the prompt
tokens. The engine's batching depends on the order (one order of a call
took twice the decode steps of another on the chip), so every seed runs
the same work, and only the tokens, and the weights, differ.

``lm_batches`` (training): ``distinct_batches`` batches of
``global_batch`` x ``seq_len`` uniformly drawn token ids plus next-token
labels, made on the device from the seed; the window cycles through them,
and no two rows are alike.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench.harness import BenchError, np_rng


def _counts(probs: List[float], n: int) -> List[int]:
    """Largest-remainder apportionment of ``n`` items to ``probs``."""
    p = np.asarray(probs, float) / float(np.sum(probs))
    raw = p * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


def length_multiset(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths in a fixed multiset: ``{"values", "probs"}`` apportions
    the values; ``{"log_uniform": [lo, hi]}`` takes the quantiles
    ``(i + 1/2) / n`` of the log-uniform distribution on ``[lo, hi]``."""
    if "values" in spec:
        out: List[int] = []
        for v, c in zip(spec["values"], _counts(spec["probs"], n)):
            out += [int(v)] * c
        return out
    if "log_uniform" in spec:
        lo, hi = spec["log_uniform"]
        return [int(round(lo * (hi / lo) ** ((i + 0.5) / n)))
                for i in range(n)]
    raise BenchError(f"unknown length distribution {spec!r}")


def prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can send, in ascending order."""
    return sorted(set(length_multiset(traffic["prompt_len"],
                                      traffic["requests_per_call"])))


def call_sizes(traffic: Dict[str, Any], call: int) -> List[Tuple[int, int]]:
    """(prompt length, output length) of each request of one call; the
    same for every seed."""
    n = traffic["requests_per_call"]
    rng = np.random.default_rng([1, call])
    plens = rng.permutation(length_multiset(traffic["prompt_len"], n))
    olens = rng.permutation(length_multiset(traffic["output_len"], n))
    return [(int(p), int(o)) for p, o in zip(plens, olens)]


def call_requests(traffic: Dict[str, Any], seed: int, call: int,
                  vocab: int) -> List[Tuple[np.ndarray, int]]:
    """(prompt token ids, output length) of each request of one call."""
    rng = np_rng(seed, 2, call)
    return [(rng.integers(0, vocab, (p,), dtype=np.int32), o)
            for p, o in call_sizes(traffic, call)]


def lm_batches(traffic: Dict[str, Any], key, vocab: int, sharding=None):
    """``distinct_batches`` batches ``{"tokens", "labels"}`` of int32
    ``(global_batch, seq_len)``, made in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    k, b, s = (traffic["distinct_batches"], traffic["global_batch"],
               traffic["seq_len"])

    def make(key):
        toks = jax.random.randint(key, (k, b, s + 1), 0, vocab, jnp.int32)
        return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
                for i in range(k)]

    out = None if sharding is None else [
        {"tokens": sharding, "labels": sharding}] * k
    return jax.jit(make, out_shardings=out)(key)

