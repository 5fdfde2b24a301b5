"""The launchers' persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise to a fixed directory
inside the checkout — never both."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import repro.launch.cache as cache

_CHILD = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
import repro.launch.cache as cache
cache.DEFAULT_DIR = Path(sys.argv[1])
print(cache.setup_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
"""


def test_default_dir_is_fixed_inside_checkout():
    root = Path(__file__).resolve().parents[1]
    assert cache.DEFAULT_DIR == root / ".jax_cache"
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_written_to_one_directory(tmp_path, env_set):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(Path(cache.__file__).parents[2]))
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD, str(default_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True).stdout
    want, other = (env_dir, default_dir) if env_set else \
        (default_dir, env_dir)
    assert out.split()[0] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
