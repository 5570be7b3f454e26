"""``use_compile_cache``: compiled programs land in
``JAX_COMPILATION_CACHE_DIR`` when it is set, and otherwise in the
checkout's git-ignored ``.jax_cache/``.  Each case runs in a fresh
interpreter, because JAX fixes its cache directory at the first compile."""

import os
import subprocess
import sys
import textwrap

from repro.core.compile_cache import CHECKOUT_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))

PROGRAM = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.core.compile_cache import use_compile_cache
    print(use_compile_cache())
    jax.jit(lambda x: x * 3 - 1)(jnp.arange(16)).block_until_ready()
""")


def _compile(env_dir=None) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", PROGRAM], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def test_env_dir_is_used_as_is(tmp_path):
    d = tmp_path / "cache"
    assert _compile(d) == str(d)
    assert any(p.name.endswith("-cache") for p in d.iterdir())


def test_default_dir_is_fixed_and_ignored():
    assert str(CHECKOUT_CACHE_DIR) == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    assert _compile() == str(CHECKOUT_CACHE_DIR)
    assert any(p.name.endswith("-cache") for p in CHECKOUT_CACHE_DIR.iterdir())
