"""The entry points' persistent compilation cache (repro.compile_cache):
``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache sits
at the fixed ``<checkout>/.jax_cache``.  Each case runs in a fresh
interpreter, because the cache settings are process-global."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SNIPPET = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _run(env, compile_: bool):
    env = dict(env, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", SNIPPET.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_cache_lives_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert _run(env, compile_=True) == [str(cache), str(cache)]
    assert any(cache.iterdir())                 # the compiled entry landed there


def test_cache_defaults_to_the_checkout():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    expected = str(ROOT / ".jax_cache")
    assert _run(env, compile_=False) == [expected, expected]
