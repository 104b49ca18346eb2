"""``chip_smoke.py`` refuses to run without a TPU, and the persistent
compilation cache lands where ``JAX_COMPILATION_CACHE_DIR`` says or else at
one fixed path inside the checkout (subprocesses: the cache directory is
process-global JAX config)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_PROBE = """
import jax, jax.numpy as jnp
import repro.launch.compile_cache as cc
print(jax.config.jax_compilation_cache_dir)
print(cc.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(args, tmp_path, **env_extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=tmp_path, env=env, timeout=300)


def test_chip_smoke_refuses_cpu(tmp_path):
    r = _run([os.path.join(REPO, "chip_smoke.py")], tmp_path)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_follows_env(tmp_path):
    cache = tmp_path / "cc"
    r = _run(["-c", CACHE_PROBE], tmp_path,
             JAX_COMPILATION_CACHE_DIR=str(cache),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(cache)] * 3
    assert any(cache.iterdir())


def test_compile_cache_default_in_checkout(tmp_path):
    probe = CACHE_PROBE.replace("jax.jit", "# jax.jit")  # config only
    r = _run(["-c", probe], tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    fixed = os.path.join(REPO, ".jax_cache")
    # importing the module sets nothing; the helper sets the fixed path
    assert r.stdout.split() == ["None", fixed, fixed]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
