"""Backend-dispatched wrappers for the Pallas kernels.

`impl` is "auto" | "pallas" | "ref". "auto" runs the compiled Pallas
kernel on a TPU and the pure-jnp oracle (`kernels.ref`) anywhere else;
"pallas" demands the kernel and raises off a TPU — it never falls back
to interpret mode, which stays reserved for kernel validation in tests
(they pass `interpret=True` to the kernel modules directly). Which
implementation a call resolves to is `resolve_impl(impl)`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import bdi as _bdi
from repro.kernels import paged_gather as _pg
from repro.kernels import qdq_int8 as _qdq
from repro.kernels import ref as _ref
from repro.kernels import residency_fused as _rf


def resolve_impl(impl: str = "auto") -> str:
    """"pallas" or "ref": what `impl` runs on the default backend.
    Raises for "pallas" off a TPU (no silent interpret fallback)."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "pallas" and not on_tpu:
        raise RuntimeError(
            f"impl='pallas' needs a TPU backend, found "
            f"{jax.default_backend()!r}; use 'ref' or 'auto' (tests call "
            "the kernel modules with interpret=True)")
    if impl not in ("auto", "pallas", "ref"):
        raise ValueError(f"impl must be auto|pallas|ref, got {impl!r}")
    return "pallas" if impl == "pallas" or on_tpu and impl == "auto" \
        else "ref"


def quantize_block_int8(x2d, impl: str = "auto"):
    if resolve_impl(impl) == "pallas":
        return _qdq.quantize_block_int8(x2d)
    return _ref.quantize_block_int8(x2d)


def dequantize_block_int8(q, scale, out_dtype=jnp.float32,
                          impl: str = "auto"):
    if resolve_impl(impl) == "pallas":
        return _qdq.dequantize_block_int8(q, scale, out_dtype=out_dtype)
    return _ref.dequantize_block_int8(q, scale, out_dtype)


def bdi_compress(x2d_i32, impl: str = "auto"):
    if resolve_impl(impl) == "pallas":
        return _bdi.bdi_compress(x2d_i32)
    return _ref.bdi_compress(x2d_i32)


def bdi_decompress(base, deltas, ok, raw, impl: str = "auto"):
    if resolve_impl(impl) == "pallas":
        return _bdi.bdi_decompress(base, deltas, ok, raw)
    return _ref.bdi_decompress(base, deltas, ok, raw)


def paged_gather(pool, idx, impl: str = "auto"):
    if resolve_impl(impl) == "pallas":
        return _pg.paged_gather(pool, idx)
    return _ref.paged_gather(pool, idx)


def paged_scatter(pool, idx, pages, *, mode=None):
    """Page-plane pool writeback — XLA's native scatter on every backend
    (no Pallas twin; see paged_gather.py's note: the bulk page plane is
    off the critical path). Inside a jitted step XLA updates the pool
    in place; this is the entry the fused transaction's ref path uses.
    mode="drop" = masked-lane convention (out-of-bounds rows no-op)."""
    return _ref.paged_scatter(pool, idx, pages, mode=mode)


def residency_fused(res, kpool, vpool, remote_k, remote_v, landed,
                    landed_pages, needed_pages, needed_writes, clock, pol,
                    impl: str = "auto"):
    """The fused per-step residency transaction (landing + victim
    selection + writeback enqueue + pool scatter + CAM probe + hit
    gather + policy touch) — see ref.fused_residency_step for the
    contract."""
    if resolve_impl(impl) == "pallas":
        return _rf.fused_residency_step(
            res, kpool, vpool, remote_k, remote_v, landed, landed_pages,
            needed_pages, needed_writes, clock, pol)
    return _ref.fused_residency_step(
        res, kpool, vpool, remote_k, remote_v, landed, landed_pages,
        needed_pages, needed_writes, clock, pol)
