"""Benchmark smoke runs under pytest: the perf code must EXECUTE, not just
import. ``benchmarks.run --smoke`` clamps every timing loop to 2 iterations
(BENCH_SMOKE=1), so a full benchmark module runs end-to-end in CI time.
"""

import json
import subprocess
import sys

import pytest

from conftest import REPO, multidev_env


def _run_bench(tmp_path, *argv, timeout=1200):
    env = multidev_env()
    env["BENCH_SMOKE"] = "1"
    env["BENCH_JSON_DIR"] = str(tmp_path)  # keep committed artifacts intact
    return subprocess.run(
        [sys.executable, "-m", *argv], capture_output=True, text=True,
        cwd=REPO, env=env, timeout=timeout)


@pytest.mark.slow
def test_bucket_path_smoke(tmp_path):
    """The 3-knob ablation (now incl. the zero1 cells) runs and emits a
    well-formed BENCH json whose wire-byte summary shows the ZeRO-1 claim:
    grad reduce_scatter + param all_gather move ≲ 0.55x the bytes of the
    f32 gradient all_reduce (bf16 wire, fp32 master shards)."""
    r = _run_bench(tmp_path, "benchmarks.bucket_path", "--devices", "8")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    path = tmp_path / "BENCH_bucket_path.json"
    assert path.is_file(), r.stdout
    doc = json.loads(path.read_text())
    assert len(doc["rows"]) == 12, "2 packs x 3 reductions x 2 plan modes"
    cells = {(row["pack"], row["reduction"], row["plan"])
             for row in doc["rows"]}
    assert ("xla", "all_reduce", "per_step") in cells
    assert ("pallas", "reduce_scatter", "persistent") in cells
    assert ("pallas", "zero1", "persistent") in cells
    s = doc["summary"]
    assert s["seed_config"] == {"pack": "xla", "reduction": "all_reduce",
                                "plan": "per_step"}
    assert s["fast_config"]["plan"] == "persistent"
    assert s["fast_ms_per_step"] > 0 and s["seed_ms_per_step"] > 0
    # the acceptance gate: zero1 per-step gradient wire bytes (param
    # all_gather counted) at num_streams=8
    assert s["zero1_wire_ratio"] <= 0.55, s
    for row in doc["rows"]:
        assert row["wire_link_bytes"] > 0, row


@pytest.mark.slow
def test_overlap_schedule_smoke(tmp_path):
    """The overlap-scheduling sweep runs end-to-end and emits a well-formed
    BENCH json whose summary shows the tentpole claim: at 8 VCIs the
    overlap schedule strictly reduces MODELED exposed-comm time vs the post
    schedule for both optimizers, while moving identical wire bytes."""
    r = _run_bench(tmp_path, "benchmarks.overlap_schedule", "--devices", "8")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    path = tmp_path / "BENCH_overlap_schedule.json"
    assert path.is_file(), r.stdout
    doc = json.loads(path.read_text())
    cells = {(row["schedule"], row["num_vcis"], row["optimizer"])
             for row in doc["rows"]}
    assert ("post", 8, "replicated") in cells
    assert ("overlap", 8, "zero1") in cells
    measured = [row for row in doc["rows"] if row["ms_per_step"] is not None]
    assert measured, "no cell ran the real train step"
    for opt in ("replicated", "zero1"):
        s = doc["summary"][opt]
        assert s["exposed_ratio_8vcis"] < 1.0, (opt, s)
        assert s["wire_bytes_equal"], (opt, s)


@pytest.mark.slow
def test_trainer_streams_smoke(tmp_path):
    """The trainer-level stream sweep executes with the fast-path knobs."""
    r = _run_bench(tmp_path, "benchmarks.trainer_streams", "--devices", "8",
                   "--pack", "pallas", "--reduction", "reduce_scatter")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "trainer_vci_streams" in r.stdout
    assert "pallas" in r.stdout


@pytest.mark.slow
def test_trainer_streams_zero1_smoke(tmp_path):
    """The trainer-level sweep executes end-to-end with the ZeRO-1 sharded
    optimizer (scatter -> sharded AdamW -> param gather on VCI streams)."""
    r = _run_bench(tmp_path, "benchmarks.trainer_streams", "--devices", "8",
                   "--optimizer", "zero1", "--zero1-wire", "bfloat16")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "trainer_vci_streams" in r.stdout
    assert "zero1" in r.stdout


@pytest.mark.slow
def test_serve_streams_smoke(tmp_path):
    """The serve-path VCI-stream benchmark runs end-to-end and emits a
    well-formed BENCH json: a tok/s cell per (arch, batch, num_vcis), and a
    shallower collective critical path with a full pool than with 1 VCI."""
    r = _run_bench(tmp_path, "benchmarks.serve_streams", "--devices", "8")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    path = tmp_path / "BENCH_serve_streams.json"
    assert path.is_file(), r.stdout
    doc = json.loads(path.read_text())
    assert doc["mesh"]["tp"] > 1
    cells = {(row["arch"], row["num_vcis"]) for row in doc["rows"]}
    assert ("olmo-1b-smoke", 1) in cells
    assert ("mixtral-8x22b-smoke", 8) in cells
    for row in doc["rows"]:
        assert row["tok_s"] > 0 and row["collectives"] > 0, row
    for arch, s in doc["summary"].items():
        # the structural claim (transfers to TPU): dedicated streams shorten
        # the collective critical path vs the single fallback stream
        assert s["depth_maxvci"] < s["depth_1vci"], (arch, s)
        assert s["tok_s_1vci"] > 0 and s["tok_s_maxvci"] > 0
    # sized-vs-full page pool engine cells: both archs, both pools,
    # admission under the mesh, and fewer resident cache bytes at equal
    # tokens in the sized pool (the paging acceptance claim)
    eng_cells = {(r["arch"], r["pool"]) for r in doc["engine_rows"]}
    for arch in ("olmo-1b-smoke", "mixtral-8x22b-smoke"):
        assert (arch, "sized") in eng_cells
        assert (arch, "full") in eng_cells
    for r in doc["engine_rows"]:
        assert r["admit_under_mesh"], r
    for key, s in doc["engine_summary"].items():
        assert s["cache_bytes_ratio"] < 1.0, (key, s)
        assert s["tok_s_sized"] > 0 and s["tok_s_full"] > 0


@pytest.mark.slow
def test_run_smoke_mode_single_benchmark(tmp_path):
    """The run.py --smoke driver executes a benchmark subprocess end-to-end."""
    env = multidev_env()
    env["BENCH_JSON_DIR"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke",
         "--only", "bucket_path", "--out", str(tmp_path / "bench")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1800)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "[ok] bucket_path" in r.stdout
    assert (tmp_path / "bench" / "bucket_path.csv").is_file()
