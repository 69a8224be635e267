"""Where ``launch/compile_cache.enable_compile_cache`` puts the persistent
compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when set, else the
fixed ``<checkout>/.jax_cache``.  Each case runs in a child process,
since the cache directory is process-wide JAX config."""
import os
import subprocess
import sys

import pytest

PROGRAM = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache(sys.argv[1]))
jax.jit(lambda x: x * 2 + 1)(jnp.arange(4)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [False, True])
def test_cache_lands_where_configured(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = checkout.resolve() / ".jax_cache"
    if from_env:
        want = tmp_path / "from_env"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", PROGRAM, str(checkout)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split() == [str(want)]
    assert any(want.iterdir()), "no cache entry written"
    assert [p.name for p in checkout.iterdir()] == \
        ([] if from_env else [".jax_cache"])
