"""The remote tier's contents: a bfloat16 value for every element, by formula.

Element `i` (flat index over pages x tokens x heads x dim) of tensor `which`
(0 for K, 1 for V) is a murmur3-mixed hash of (seed, which, i), read as a
bfloat16 of magnitude in [1, 2) with a hashed sign and 7 hashed mantissa
bits. The store under test gets the whole tier, built on the device in one
jitted call; the checks recompute any row from the same formula, so they
never read what the program holds. The same code runs on numpy and on
jax.numpy (`xp`).
"""
from __future__ import annotations

import numpy as np

_M1, _M2, _GOLD = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B1


def _mix(h, xp):
    h = h ^ (h >> 16)
    h = h * xp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * xp.uint32(_M2)
    return h ^ (h >> 16)


def seed_words(seed: int, which: int) -> int:
    """A 32-bit salt from a seed of any size and the tensor index."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return (lo ^ (hi * 0x27D4EB2F) ^ (which * 0x165667B1)) & 0xFFFFFFFF


def bits(flat_index, salt, xp=np):
    """uint16 bfloat16 bit patterns of the given flat element indices;
    `salt` is `seed_words(...)` as a uint32 (traced under jit, so one
    compiled program serves every seed)."""
    i = flat_index.astype(xp.uint32)
    h = _mix(i * xp.uint32(_GOLD) + salt, xp)
    return ((h & xp.uint32(0x807F)) | xp.uint32(0x3F80)).astype(xp.uint16)


def rows(page_ids, row_shape, salt, xp=np):
    """bfloat16 rows (len(page_ids),) + row_shape of the tier."""
    n = int(np.prod(row_shape))
    page_ids = xp.asarray(page_ids).astype(xp.uint32)
    idx = (page_ids[:, None] * xp.uint32(n)
           + xp.arange(n, dtype=xp.uint32)[None, :])
    b = bits(idx, xp.asarray(salt, dtype=xp.uint32), xp)
    return _as_bf16(b, xp).reshape((page_ids.shape[0],) + tuple(row_shape))


def _as_bf16(b, xp):
    if xp is np:
        import ml_dtypes
        return b.view(ml_dtypes.bfloat16)
    import jax
    return jax.lax.bitcast_convert_type(b, xp.bfloat16)
