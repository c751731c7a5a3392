"""A lowered program kept by `jax.export` gives what the program gives,
is read back on the next run, and is missed after an edit of the source."""
import jax
import jax.numpy as jnp
import numpy as np

import exported


def _fn(x, pair):
    return {"a": jnp.cumsum(x) * pair[0], "b": x.sum() + pair[1]}


def test_export_kept_and_read_back(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "m.py").write_text("x = 1\n")
    args = (jnp.arange(6.0), (jnp.float32(2.0), jnp.float32(3.0)))
    want = _fn(*args)
    lo, hit = exported.lower("t", _fn, args, "k", tmp_path / "c", src)
    assert not hit
    got = lo.compile()(*jax.tree.leaves(args))
    lo2, hit2 = exported.lower("t", _fn, args, "k", tmp_path / "c", src)
    assert hit2
    got2 = lo2.compile()(*jax.tree.leaves(args))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
        np.testing.assert_array_equal(got2[k], want[k])
    (src / "m.py").write_text("x = 2\n")
    _, hit3 = exported.lower("t", _fn, args, "k", tmp_path / "c", src)
    _, hit4 = exported.lower("t", _fn, args, "other", tmp_path / "c", src)
    assert not hit3 and not hit4
    assert len(list((tmp_path / "c").glob("*.jaxexport"))) == 3
