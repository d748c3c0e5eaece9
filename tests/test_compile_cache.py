"""The entry points' persistent compilation cache: the directory the
environment names, else a fixed one inside the checkout. Each case runs
in a child process, so the cache never switches on in the test worker."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
_SRC = os.path.join(_ROOT, "src")

_PROBE = """
import sys
from repro.launch import compile_cache
path = compile_cache.use_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
assert jax.config.jax_compilation_cache_dir == path, path
if sys.argv[1] == "compile":
    jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)))
print(path)
"""


def _probe(env_dir, action, prefix=""):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", prefix + _PROBE, action],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("prefix", ["", "import jax\n"],
                         ids=["before_jax", "after_jax"])
def test_env_dir_is_used_and_receives_entries(tmp_path, prefix):
    assert _probe(tmp_path, "compile", prefix) == str(tmp_path)
    assert os.listdir(tmp_path), "no compiled entry landed in the cache"


def test_unset_env_falls_back_to_the_checkout():
    from repro.launch import compile_cache
    path = _probe(None, "none")
    assert path == compile_cache.CHECKOUT_CACHE_DIR
    assert os.path.realpath(path) == os.path.realpath(
        os.path.join(_ROOT, ".jax_cache"))
