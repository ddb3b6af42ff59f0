"""The chip entry point and the compile cache, checked without a chip:
``chip_smoke.py`` refuses to run anywhere but a TPU, and
``enable_compile_cache`` keeps JAX's persistent cache at one fixed path."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import CACHE_DIR, ENV_VAR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """On the CPU the smoke exits non-zero and never prints the ok line,
    both from the repo and as a lone copy of the script."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout[-2000:]
    lines = r.stdout.strip().splitlines()
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
        assert not (isinstance(last, dict) and last.get("ok")), lines[-1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert enable_compile_cache() == CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    assert CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert compile_cache.REPO_ROOT == REPO


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """A process started with the variable set writes its entries there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{
        ENV_VAR: str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "PYTHONPATH": os.path.join(REPO, "src")})
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path)
