"""The entry points' persistent compile cache lands where it should:
in `JAX_COMPILATION_CACHE_DIR` when that is set, else in the fixed,
gitignored directory inside the checkout. Each case runs in a fresh
interpreter, since the cache directory is fixed at a process's first
compile."""
import os
import shutil
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
    def f(x):
        with jax.named_scope({scope!r}):
            return jnp.sin(x) * 2.0
    print("scope.a" in jax.jit(f).lower(jnp.arange(8.0)).compile().as_text())
"""


def _env(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    return env


def _probe(env_extra, compile_, scope="scope.a"):
    env = _env(env_extra)
    r = subprocess.run([sys.executable, "-c",
                        PROBE.format(compile=compile_, scope=scope)],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "xla_cache"
    returned, configured, _ = _probe(
        {"JAX_COMPILATION_CACHE_DIR": str(cache),
         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}, True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir()), "nothing was cached"


def test_cache_key_covers_scope_names(tmp_path):
    """A program that differs from a cached one only in a scope's name
    compiles anew: loading the cached executable would hand it the
    other program's op_names, and a profile would read wrong phases."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    assert _probe(env, True, "scope.a")[2] == "True"
    assert _probe(env, True, "scope.b")[2] == "False"


def test_cache_entry_found_from_a_moved_checkout(tmp_path):
    """The same program compiled from a copy of the checkout elsewhere
    loads the entry the first copy wrote: the key's source paths are
    relative to the checkout."""
    cache = tmp_path / "xla_cache"
    env = {"JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    probe = (PROBE.format(compile=False, scope="") + "from repro.core "
             "import residency\njax.jit(residency.init_residency, "
             "static_argnums=(0, 1))(2, 4)\n")
    entries = []
    for where in ("a", "b"):
        shutil.copytree(ROOT / "src", tmp_path / where / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
        r = subprocess.run([sys.executable, "-c", probe],
                           capture_output=True, text=True, timeout=300,
                           env=dict(_env(env), PYTHONPATH=str(
                               tmp_path / where / "src")))
        assert r.returncode == 0, r.stderr
        entries.append(sorted(p.name for p in cache.iterdir()))
    assert entries[0] and entries[1] == entries[0]


def test_cache_defaults_to_gitignored_checkout_dir():
    from repro.launch.compile_cache import DEFAULT_DIR
    returned, configured = _probe({}, False)
    assert returned == configured == str(DEFAULT_DIR)
    assert DEFAULT_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored
