"""Where the persistent compilation cache goes (repro.launch.compile_cache).

Each case runs in a fresh interpreter: JAX fixes its cache directory at the
first compile of a process.
"""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
"""


def _run(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_var_places_the_cache(tmp_path):
    where = tmp_path / "jax-cache"
    used, configured = _run({"JAX_COMPILATION_CACHE_DIR": str(where)})
    assert used == configured == str(where)
    assert any(p.name.endswith("-cache") for p in where.iterdir())


def test_default_is_the_fixed_checkout_path():
    from repro.launch.compile_cache import CHECKOUT_CACHE
    assert CHECKOUT_CACHE == ROOT / ".jax_cache"
    used, configured = _run({})
    assert used == configured == str(ROOT / ".jax_cache")
    assert any(p.name.endswith("-cache") for p in CHECKOUT_CACHE.iterdir())
