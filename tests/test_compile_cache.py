"""`repro.compile_cache`: the persistent compile cache lands where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``.

Each case runs in a fresh interpreter: JAX fixes its cache directory at
the first compile of a process.
"""

import os
import pathlib
import subprocess
import sys


REPO = pathlib.Path(__file__).resolve().parents[1]

PROGRAM = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", PROGRAM if env_dir is not None
         else PROGRAM.split("jax.jit")[0]],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    )
    return out.stdout.split()


def test_cache_follows_environment(tmp_path):
    used, configured = _run(tmp_path)
    assert used == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_cache_defaults_to_checkout():
    used, configured = _run(None)
    assert used == configured == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
