"""Lowered programs kept across runs, inside the checkout (`jax.export`).

Tracing and lowering a large program can take longer than compiling it
from the persistent compile cache. `lower` exports the program the first
time, keeps the serialized export in `cache_dir`, and later lowers only a
call of it. The file's name holds a digest of the program's source tree,
the static key and the arguments' tree, shapes and dtypes, so an edit of
the program misses the cache instead of running stale code.
"""
from __future__ import annotations

import hashlib
from pathlib import Path


def _source_digest(src_root: Path) -> str:
    h = hashlib.sha256()
    for p in sorted(src_root.rglob("*.py")):
        h.update(str(p.relative_to(src_root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def lower(name: str, fn, args: tuple, key: str, cache_dir: Path,
          src_root: Path):
    """(lowered, hit): `fn(*args)` lowered through its export, and whether
    the export came from `cache_dir`. The compiled program takes the
    flattened leaves of `args`."""
    import jax
    from jax import export
    leaves, treedef = jax.tree.flatten(args)
    sig = repr([key, str(treedef), jax.__version__]
               + [f"{x.shape}{x.dtype}" for x in leaves])
    digest = hashlib.sha256((_source_digest(src_root) + sig).encode())
    path = Path(cache_dir) / f"{name}-{digest.hexdigest()[:32]}.jaxexport"
    hit = path.exists()
    if hit:
        exp = export.deserialize(bytearray(path.read_bytes()))
    else:
        flat = jax.jit(lambda *xs: fn(*jax.tree.unflatten(treedef, xs)))
        exp = export.export(flat)(*leaves)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_bytes(exp.serialize())
        tmp.replace(path)
    return jax.jit(exp.call).lower(*leaves), hit
