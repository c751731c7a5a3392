"""The entry points' persistent compile cache lands where it should:
in `JAX_COMPILATION_CACHE_DIR` when that is set, else in the fixed,
gitignored directory inside the checkout. Each case runs in a fresh
interpreter, since the cache directory is fixed at a process's first
compile."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile!r}:
    jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0)).block_until_ready()
"""


def _probe(env_extra, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c",
                        PROBE.format(compile=compile_)],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "xla_cache"
    returned, configured = _probe(
        {"JAX_COMPILATION_CACHE_DIR": str(cache),
         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}, True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir()), "nothing was cached"


def test_cache_defaults_to_gitignored_checkout_dir():
    from repro.launch.compile_cache import DEFAULT_DIR
    returned, configured = _probe({}, False)
    assert returned == configured == str(DEFAULT_DIR)
    assert DEFAULT_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored
