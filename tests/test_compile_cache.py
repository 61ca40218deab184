"""The entry points' persistent compile cache (``repro.compile_cache``).

Each case turns the cache on in a child process, never in the test
process, and checks where the compiled program was written.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

CHILD = """
import sys
import jax
import jax.numpy as jnp
from repro import compile_cache
compile_cache.CHECKOUT = sys.argv[1]          # a checkout under tmp_path
print(compile_cache.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(5.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [False, True],
                         ids=["checkout", "env"])
def test_cache_written_where_documented(tmp_path, from_env):
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/
    .jax_cache``; nothing is written to the other place."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (SRC, env.get("PYTHONPATH")) if p))
    in_checkout = tmp_path / ".jax_cache"
    from_var = tmp_path / "from_env"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(from_var)
    out = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True)
    want, other = ((from_var, in_checkout) if from_env
                   else (in_checkout, from_var))
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()
