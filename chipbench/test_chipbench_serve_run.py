"""Whole serving runs of a tiny cell on the CPU, the chip check skipped:
a sound run is correct; a token altered where it is produced is not; the
float8 control is not; and without a TPU the command refuses to run."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import tiny
from chipbench.harness import ROOT, SRC


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def test_sound_run_is_correct(root):
    out = tiny.run_cell(root, "tiny-serve", seed=2 ** 31 + 77)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 6
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["widest_gap"]["value"] < \
        out["checks"]["widest_gap"]["limit"]


def test_altered_token_is_caught(root):
    out = tiny.run_cell(root, "tiny-serve", fault="token")
    assert out["correct"] is False


CONTROL = """
import sys, contextlib
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import harness
from chipbench.entries import serve
cell = harness.load_cell("tiny-serve", {tmp!r})
run = serve.Run(cell, 5, jax.devices()[:1])
run.setup()
run.window(0.5, lambda _: contextlib.nullcontext())
run.free()
print(float(run.gaps()[0].max()), float(run.gaps(control=True)[0].max()),
      cell.settings["check"]["limits"]["widest_gap"])
"""


def test_float8_control_fails_the_limit(root):
    code = CONTROL.format(root=ROOT, src=SRC, tmp=root)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-3000:]
    program, control, limit = map(float, r.stdout.split()[-3:])
    assert program < limit < control


def test_refuses_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "olmo1b-serve-decode", "--seed", "1", "--seconds",
         "1"], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
