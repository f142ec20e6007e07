"""Placement of JAX's persistent compilation cache (``repro.compile_cache``).

Each case runs in a fresh CPU process, so turning the cache on never leaks
into the rest of the suite.  The probe compiles a function with a fresh
constant, so its entry is new wherever the cache is."""
import os
import random
import subprocess
import sys
import textwrap

from repro import compile_cache

PROBE = textwrap.dedent("""
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    c = float(sys.argv[1])
    jax.jit(lambda x: x * 3 + c)(jnp.ones(4)).block_until_ready()
""")


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[compile_cache.ENV] = env_dir
    r = subprocess.run([sys.executable, "-c", PROBE, repr(random.random())],
                       capture_output=True, text=True, timeout=300,
                       cwd=compile_cache.REPO_ROOT, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.split()


def test_environment_directory_is_the_cache(tmp_path):
    before = _entries(compile_cache.DEFAULT_DIR)
    chosen, configured = _probe(str(tmp_path))
    assert chosen == configured == str(tmp_path)
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path))
    assert _entries(compile_cache.DEFAULT_DIR) == before


def test_default_is_a_fixed_directory_in_the_repo():
    before = _entries(compile_cache.DEFAULT_DIR)
    chosen, configured = _probe(None)
    assert chosen == configured == os.path.join(compile_cache.REPO_ROOT,
                                                ".jax_cache")
    assert os.path.exists(os.path.join(compile_cache.REPO_ROOT,
                                       "chip_smoke.py"))
    assert any(f.endswith("-cache")
               for f in _entries(compile_cache.DEFAULT_DIR) - before)
