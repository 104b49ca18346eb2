"""Serving cells: ``ServeEngine.generate`` over closed calls of requests.

Set-up makes the weights on the device from the seed, builds the engine as
the cell file says, and warms every program the cell's traffic can reach:
through ``generate`` itself each batch-prefill width and each admission
width, and directly the page allocator's programs for each count of live
slots and pages. The window then runs whole calls of the cell's traffic;
no call starts after ``--seconds``.

``check`` takes a sample of the window's finished requests drawn from the
seed, the longest among them, runs the float32 reference once over each
prompt with its served tokens, and reads the widest gap by which a served
token's logit lies below the reference's best at that position.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import traffic as traffic_mod
from chipbench.harness import Compared, jax_key, model_config, np_rng, say
from chipbench.reference import family


def warm_calls(lengths: List[int], batch: int, max_len: int
               ) -> List[List[Tuple[int, int]]]:
    """(prompt length, output length) of the warm-up calls: per prompt
    length ``w`` one call whose batch pads to ``w`` and holds every shorter
    length beside it (each batch-prefill width and each first page
    allocation), while slot 0 admits one request of every length up to
    ``w`` in turn (each admission width, at a page-aligned cursor and at
    unaligned ones)."""
    calls = []
    for w in lengths:
        shorter = [x for x in lengths if x <= w]
        admits = [w] + shorter * 2
        stay = len(admits) + 2
        if w + stay > max_len:
            raise ValueError(f"prompt length {w} leaves no room to warm up "
                             f"(max_len {max_len})")
        reqs = [(w, 1)] + [(shorter[i % len(shorter)], stay)
                           for i in range(batch - 1)]
        calls.append(reqs + [(x, 1) for x in admits])
    return calls


def warm_page_tables(batch: int, max_len: int, page_size: int) -> None:
    """Programs of the page allocator that depend on counts the traffic
    varies: the decode page-boundary allocation (one program per count of
    live slots, 1 to ``batch``) and the range of logical pages a prefill or
    admission maps (``jnp.arange(lo, hi + 1)``, one program per length and
    another where ``lo`` is not 0). Arguments are made as the engine makes
    them."""
    import jax.numpy as jnp
    from repro.serve.paging import alloc_step_pages_jit, page_state_init

    max_pages = -(-max_len // page_size)
    st = page_state_init(1 + batch * max_pages, batch, max_pages)
    for m in range(1, batch + 1):
        alloc_step_pages_jit(st, jnp.asarray(list(range(m)), jnp.int32),
                             jnp.asarray(0, jnp.int32))
    for n in range(1, max_pages + 1):
        jnp.arange(0, n, dtype=jnp.int32)
        jnp.arange(1, n + 1, dtype=jnp.int32)


class Run:
    def __init__(self, cell, seed: int, devs):
        self.cell, self.seed, self.devs = cell, seed, devs
        self.cfg_dict = cell.config
        self.cfg = model_config(cell.config)
        self.eng = cell.settings["engine"]
        self.traffic = cell.traffic
        self.finished: List[Tuple[np.ndarray, np.ndarray, int]] = []

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.serve.engine import Request, ServeEngine

        ref = family(self.cfg_dict["reference"])
        self.params = jax.jit(lambda k: ref.init_params(self.cfg_dict, k))(
            jax_key(self.seed))
        jax.block_until_ready(self.params)
        self.engine = ServeEngine(self.cfg, self.params, **self.eng)
        lengths = traffic_mod.prompt_lengths(self.traffic)
        calls = warm_calls(lengths, self.eng["batch_size"],
                           self.eng["max_len"])
        rng = np_rng(self.seed, 9)
        for sizes in calls:
            self.engine.generate([
                Request(prompt=rng.integers(0, self.cfg.vocab_size, (p,),
                                            dtype=np.int32),
                        max_new_tokens=o) for p, o in sizes])
        if self.eng.get("paged"):
            warm_page_tables(self.eng["batch_size"], self.eng["max_len"],
                             self.eng["page_size"])
        say(f"[serve] warm-up: {len(calls)} calls over prompt lengths "
            f"{lengths}")

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, annotate) -> Dict[str, Any]:
        from repro.serve.engine import Request

        v = self.cfg.vocab_size
        tokens = attempted = failed = 0
        calls = []
        t0 = time.perf_counter()
        i = 0
        while i == 0 or time.perf_counter() - t0 < seconds:
            reqs = [Request(prompt=p, max_new_tokens=o)
                    for p, o in traffic_mod.call_requests(
                        self.traffic, self.seed, i, v)]
            with annotate(f"generate call {i}"):
                c0 = time.perf_counter()
                self.engine.generate(reqs)
                calls.append(time.perf_counter() - c0)
            for r in reqs:
                got = 0 if r.generated is None else len(r.generated)
                attempted += 1
                tokens += got
                if got != r.max_new_tokens:
                    failed += 1
                else:
                    self.finished.append((r.prompt, r.generated,
                                          r.max_new_tokens))
            i += 1
        elapsed = time.perf_counter() - t0
        say(f"[serve] window: {i} calls in {elapsed:.3f} s "
            f"({', '.join(f'{c:.3f}' for c in calls)}), {tokens} tokens")
        return {"elapsed_s": elapsed, "attempted": attempted,
                "failed": failed, "tokens": tokens,
                "metrics": {"serve_tokens_per_s": tokens / elapsed}}

    def layer_inputs(self) -> Dict[str, Any]:
        """What the per-layer readers need besides the trace: the sizes of
        every request the window served."""
        return {"requests": [(len(p), len(g)) for p, g, _ in self.finished],
                "engine": self.eng, "cache_dtype": "float32"}

    def free(self) -> None:
        """Drop the engine and its compiled programs: a loaded TPU program
        holds its scratch memory until it is unloaded."""
        import jax

        del self.engine
        jax.clear_caches()

    # -- correctness -------------------------------------------------------
    def sample(self) -> List[int]:
        """Indices of the finished requests the check compares: the one
        with the most served tokens and others drawn from the seed."""
        n = self.cell.settings["check"]["sample"]
        order = np.argsort([-len(g) for _, g, _ in self.finished],
                           kind="stable")
        rest = np_rng(self.seed, 5).permutation(order[1:])
        return [int(order[0])] + [int(i) for i in rest[: n - 1]]

    def sequences(self, idx):
        """(tokens, targets, mask) of the sampled requests, right-padded to
        the cache depth: the reference sees the prompt and every served
        token but the last, and is read where each served token was chosen."""
        t = self.eng["max_len"]
        n = len(idx)
        tok = np.zeros((n, t), np.int32)
        tgt = np.zeros((n, t), np.int32)
        mask = np.zeros((n, t), bool)
        for row, i in enumerate(idx):
            p, g, _ = self.finished[i]
            seq = np.concatenate([p, g[:-1]])
            tok[row, : len(seq)] = seq
            tgt[row, len(p) - 1: len(p) - 1 + len(g)] = g
            mask[row, len(p) - 1: len(p) - 1 + len(g)] = True
        return tok, tgt, mask

    def gaps(self, control: bool = False):
        """Per served token: the reference's best logit minus the logit of
        the served token (or, for the control, of the token the float8
        reference ranks first)."""
        import jax
        import jax.numpy as jnp

        ref = family(self.cfg_dict["reference"])
        cfg = self.cfg_dict

        @jax.jit
        def run(params, tok, tgt):
            logits = ref.forward(params, tok, cfg)
            best = logits.max(-1)
            if control:
                tgt = ref.forward(params, tok, cfg, control=True).argmax(-1)
            chosen = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
            return best - chosen

        idx = self.sample()
        tok, tgt, mask = self.sequences(idx)
        g = np.asarray(run(self.params, tok, tgt))
        return g[mask], int(mask.sum())

    def check(self) -> List[Compared]:
        limits = self.cell.settings["check"]["limits"]
        t0 = time.perf_counter()
        g, n = self.gaps()
        widest = float(g.max()) if n else float("inf")
        say(f"[serve] check: {n} served tokens of {len(self.sample())} "
            f"requests against the reference in "
            f"{time.perf_counter() - t0:.3f} s")
        return [Compared("widest_gap", widest, limits["widest_gap"])]
