"""Tiny cells for the CPU tests: the benchmark's own code on a two-layer
model, so a whole run (set-up, window, check) fits a test. ``make_root``
writes a checkout-like directory holding them; ``run_cell`` runs one in a
fresh process with the chip check skipped, optionally with a fault planted
in the program first, and returns the result line."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict

from chipbench.harness import ROOT, SRC, benchmark

CONFIG = {"reference": "dense", "name": "tiny", "family": "dense",
          "num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 4,
          "head_dim": 32, "d_ff": 256, "vocab_size": 256,
          "hidden_act": "silu", "norm": "nonparametric", "norm_eps": 1e-5,
          "use_bias": False, "tie_embeddings": True, "rope_theta": 10000.0,
          "dtype": "bfloat16", "param_dtype": "bfloat16",
          "optimizer_dtype": "float32", "reduced": []}
OPTIMIZER = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1, "max_grad_norm": 1.0}
TRAIN_LIMITS = {"loss_gap": 3e-4, "grad_gap": 5e-3, "change_gap": 0.2}
CELLS = {
    "tiny-serve": {
        "entry": "serve", "config": "tiny", "traffic": "tiny-chat",
        "chips": 1,
        "engine": {"batch_size": 4, "max_len": 96, "paged": True,
                   "page_size": 16},
        "check": {"sample": 4, "limits": {"widest_gap": 0.02}}},
    "tiny-train": {
        "entry": "train", "config": "tiny", "traffic": "tiny-lm", "chips": 1,
        "step": {"comm": "gspmd"}, "optimizer": OPTIMIZER,
        "check": {"steps": 3, "rows_per_block": 2,
                  "limits": TRAIN_LIMITS}},
    "tiny-train-dp4": {
        "entry": "train", "config": "tiny", "traffic": "tiny-lm", "chips": 4,
        "step": {"comm": "vci", "optimizer": "zero1", "schedule": "overlap",
                 "token_impl": "data"},
        "optimizer": OPTIMIZER,
        "check": {"steps": 3, "rows_per_block": 4,
                  "limits": TRAIN_LIMITS}},
}
TRAFFIC = {
    "tiny-chat": {"kind": "closed_calls", "requests_per_call": 6,
                  "prompt_len": {"values": [16, 32], "probs": [0.5, 0.5]},
                  "output_len": {"log_uniform": [4, 32]}},
    "tiny-lm": {"kind": "lm_batches", "global_batch": 8, "seq_len": 32,
                "distinct_batches": 4},
}


def _write(path: str, obj: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(root: str) -> str:
    """A directory laid out like a checkout, whose BENCHMARK.json lists the
    tiny cells in place of the real ones (same metrics)."""
    base = os.path.join(root, "chipbench")
    _write(os.path.join(base, "configs", "tiny.json"), CONFIG)
    for name, t in TRAFFIC.items():
        _write(os.path.join(base, "traffic", f"{name}.json"), t)
    for name, c in CELLS.items():
        _write(os.path.join(base, "cells", f"{name}.json"), c)
    bench = benchmark()
    bench["workloads"] = [{"name": n, "config": c["config"],
                           "traffic": c["traffic"], "chips": c["chips"],
                           "why": "test"} for n, c in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n, c in CELLS.items()
                              if c["entry"] in m["name"]]
    bench["end_to_end"].append({
        "name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": [n for n, c in CELLS.items() if c["entry"] == "train"]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


# Faults planted in the program before a run; each breaks what the
# timed path produces in one way the check must catch.
FAULTS = {
    "": "",
    # a token altered where it is produced
    "token": """
import repro.serve.engine as e
_sel = e.select_tokens
e.select_tokens = lambda *a, **k: (_sel(*a, **k) + 1) % {vocab}
""",
    # a step that returns its state unchanged
    "frozen": """
import repro.train.trainer as t
_mk = t.make_train_step
def _frozen(*a, **k):
    f = _mk(*a, **k)
    return lambda state, batch: (state, f(state, batch)[1])
t.make_train_step = _frozen
""",
    # half of the batch left out, the mean taken over the rest
    "half": """
import repro.train.trainer as t
_mk = t.make_train_step
def _half(*a, **k):
    f = _mk(*a, **k)
    def step(state, batch):
        n = batch["tokens"].shape[0] // 2
        return f(state, {{key: v[:n] for key, v in batch.items()}})
    return step
t.make_train_step = _half
""",
    # the exchange between chips left out: each chip keeps its own
    # gradient's shard (times the chip count, so the mean is its own)
    "local": """
import jax
from repro.core import collectives
def _local(self, x, ctx, *, axis, scatter_axis=0):
    n = jax.lax.axis_size(axis)
    size = x.shape[scatter_axis] // n
    return jax.lax.dynamic_slice_in_dim(
        x, jax.lax.axis_index(axis) * size, size, scatter_axis) * n
collectives.CommRuntime.reduce_scatter = _local
""",
}

DRIVER = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
{fault}
import importlib.util
spec = importlib.util.spec_from_file_location("run", {run!r})
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
sys.exit(run.main(sys.argv[1:], root={tmp!r}, require_tpu=False))
"""


def run_cell(tmp_root: str, cell: str, seed: int = 3, seconds: float = 1.0,
             fault: str = "", devices: int = 1) -> Dict[str, Any]:
    """Run a tiny cell in a fresh process on ``devices`` CPU devices and
    return its parsed result line."""
    code = DRIVER.format(root=ROOT, src=SRC, tmp=tmp_root,
                         run=os.path.join(ROOT, "chipbench", "run.py"),
                         fault=FAULTS[fault].format(
                             vocab=CONFIG["vocab_size"]))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(tmp_root, "cache"))
    r = subprocess.run([sys.executable, "-c", code, "--workload", cell,
                        "--seed", str(seed), "--seconds", str(seconds)],
                       capture_output=True, text=True, env=env, timeout=600)
    lines = r.stdout.strip().splitlines()
    if r.returncode != 0 or not lines:
        raise RuntimeError(f"run failed ({r.returncode}):\n{r.stderr[-3000:]}")
    return json.loads(lines[-1])
