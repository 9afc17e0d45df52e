"""Where `repro.launch.compile_cache` keeps JAX's persistent cache."""
import os
import subprocess
import sys

from repro.launch import compile_cache

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
REPO_SRC = os.path.join(REPO_ROOT, "src")

# one jitted program; prints whether this process hit the cache
_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(16)).block_until_ready()
print("/jax/compilation_cache/cache_hits" in events)
"""


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=REPO_SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    cache_dir, hit = out.stdout.split()[-2:]
    return cache_dir, hit == "True"


def test_default_dir_is_fixed_inside_the_checkout():
    assert compile_cache.CHECKOUT_CACHE_DIR == os.path.join(REPO_ROOT,
                                                            ".jax_cache")


def test_env_dir_is_used_and_hit_by_a_second_run(tmp_path):
    env_dir = str(tmp_path / "cache")
    assert _probe(env_dir) == (env_dir, False)
    assert os.listdir(env_dir)
    assert _probe(env_dir) == (env_dir, True)
