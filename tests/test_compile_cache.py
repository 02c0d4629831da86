"""Where `repro.compile_cache.use_compile_cache` puts JAX's persistent cache.

Each case compiles in a child process (the cache directory is process
state once JAX has initialised it) and checks where the entries land.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_CHILD = """
import pathlib, sys
import repro.compile_cache as cc
cc.CHECKOUT = pathlib.Path(sys.argv[1])
print(cc.use_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_lands_in_its_directory(tmp_path, env_set):
    checkout = tmp_path / "checkout"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_set:
        want = tmp_path / "from_env"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    else:
        want = checkout / ".jax_cache"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CHILD), str(checkout)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[0] == str(want)
    assert want.is_dir() and any(want.iterdir())
    # nothing lands at the checkout's default path when the env names one
    assert env_set != (checkout / ".jax_cache").exists()
