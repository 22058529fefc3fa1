"""The persistent compile cache lands where JAX_COMPILATION_CACHE_DIR
says, or at the checkout's fixed ``.jax_cache`` when it is unset."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.utils.compile_cache import CHECKOUT_CACHE_DIR

_SCRIPT = """
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.tanh(x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
"""


def _run(env: dict) -> list:
    env = dict(env, PYTHONPATH=str(Path(__file__).parent.parent / "src"),
               JAX_PLATFORMS="cpu",
               # cache every compile, however quick, so landing is visible
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


@pytest.mark.parametrize("placed", ["outside", "checkout"])
def test_compile_cache_location(tmp_path, placed):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed == "outside":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        where = tmp_path / "cache"
    else:
        where = CHECKOUT_CACHE_DIR
    assert _run(env) == [str(where), str(where)]
    assert any(where.iterdir())
