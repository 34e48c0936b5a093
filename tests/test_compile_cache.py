"""Where the entry points keep JAX's persistent compilation cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
                **env)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=full, timeout=300, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_checkout_dir_is_fixed_inside_the_repo():
    assert CHECKOUT_CACHE_DIR == Path(ROOT).resolve() / ".jax_cache"


def test_default_is_the_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax_and_written(tmp_path):
    got = _python(
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "d = enable_compile_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n"
        "print(d)\n",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert got == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_importing_repro_sets_no_cache():
    got = _python(
        "import jax, repro, repro.api, repro.core\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    assert got == "None"
