"""What every cell shares: finding a cell's files by name, the device check,
the compile counter, seeds, the peak table and the result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")


class BenchError(RuntimeError):
    """A run that cannot be made: no chip, an unknown cell, a missing file."""


def load_json(*parts: str) -> Dict[str, Any]:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it names."""

    name: str
    chips: int
    settings: Dict[str, Any]      # cells/<name>.json
    config: Dict[str, Any]        # configs/<config>.json
    traffic: Dict[str, Any]       # traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def entry(self) -> str:
        return self.settings["entry"]


def _for_cell(metrics, name: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str, root: str = ROOT, listed: bool = True) -> Cell:
    """The cell ``name``: its entry in ``<root>/BENCHMARK.json`` and the
    files it names under ``<root>/chipbench``. A cell file repeats its
    config, traffic and chips; the two must agree. ``listed=False`` also
    takes a cell that BENCHMARK.json does not list (it then has no
    metrics), for the calibration script."""
    base = os.path.join(root, "chipbench")
    settings = load_json(base, "cells", f"{name}.json")
    bench = benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries and listed:
        raise BenchError(f"workload {name!r} is not in BENCHMARK.json")
    w = entries[0] if entries else settings
    for key in ("config", "traffic", "chips"):
        if w[key] != settings[key]:
            raise BenchError(f"cells/{name}.json says {key}={settings[key]!r}"
                             f" but BENCHMARK.json says {w[key]!r}")
    return Cell(name=name, chips=int(w["chips"]), settings=settings,
                config=load_json(base, "configs", f"{w['config']}.json"),
                traffic=load_json(base, "traffic", f"{w['traffic']}.json"),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def model_config(config: Dict[str, Any]):
    """The program's ModelConfig built from a configuration file: every key
    that names a ModelConfig field is taken as it stands."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in config.items() if k in fields}
    if isinstance(kw.get("ssm"), dict):
        kw["ssm"] = SSMConfig(**kw["ssm"])
    if isinstance(kw.get("moe"), dict):
        kw["moe"] = MoEConfig(**kw["moe"])
    return ModelConfig(**kw)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's peaks; a kind that is not in the table is an error."""
    table = load_json(HERE, "peaks.json")
    if device_kind not in table:
        raise BenchError(f"device_kind {device_kind!r} is not in peaks.json "
                         f"(known: {sorted(table)})")
    return table[device_kind]


def seed_words(seed: int, n: int = 1) -> np.ndarray:
    """``n`` uint32 words drawn from any whole-number seed (negative or wider
    than 64 bits included): the same seed always gives the same words."""
    return np.random.SeedSequence(abs(int(seed))
                                  + (1 << 80 if seed < 0 else 0)
                                  ).generate_state(n)


def jax_key(seed: int, salt: int = 0):
    import jax

    return jax.random.key(int(seed_words(seed, salt + 1)[salt]))


def np_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(w) for w in seed_words(seed, 2)]
                               + list(stream)))


class CompileClock:
    """Counts the programs JAX lowers (every new jit specialisation, whether
    or not the persistent cache then holds it) and sums backend compile
    seconds; ``names`` records what was lowered while ``recording``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        import logging

        self.lowered = 0
        self.compile_s = 0.0
        self.names: List[str] = []
        self.recording = False
        jax.monitoring.register_event_duration_secs_listener(self._on)
        clock = self

        class _Names(logging.Handler):
            def emit(self, record):
                if clock.recording:
                    clock.names.append(record.getMessage()[:160])

        # jax_log_compiles names each program as it is compiled; the message
        # is kept only while the window runs, and printed nowhere else.
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch",
                     "jax._src.compiler"):
            log = logging.getLogger(name)
            log.addHandler(_Names())
            log.propagate = False
        jax.config.update("jax_log_compiles", True)

    def _on(self, event, secs, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compile_s += secs


def require_chips(chips: int):
    """The devices of the cell. Anything but a TPU, or fewer chips than the
    cell asks for, is an error: nothing is measured elsewhere."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX reports platform "
                         f"{devs[0].platform!r}; this benchmark runs on a TPU "
                         f"only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX reports "
                         f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_reader(metric: str) -> Callable:
    """metrics/<metric>.py's ``read(ctx)``: the metric's value, or None where
    the run holds nothing to read."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no reader metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Compared:
    """One number of the correctness check beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                compared: List[Compared],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in compared}
    return json.dumps(out)
