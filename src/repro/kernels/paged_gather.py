"""Pallas TPU kernel: paged KV gather — the DaeMon sub-block critical plane.

Gathers pages (or single-token sub-blocks) from the HBM-resident pool into
a contiguous VMEM-backed output, driven by a scalar-prefetched index list —
the page table is known before the grid runs, so the TPU can pipeline the
HBM->VMEM copies (this is the "fetch the requested line straight into the
LLC" path of the paper, in TPU clothes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, pool_ref, out_ref):
    del idx_ref  # consumed by the index_map (scalar prefetch)
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(pool, idx, *, interpret: bool = False):
    """pool: (P, page, H, D); idx: (L,) int32 -> (L, page, H, D)."""
    p, page, h, d = pool.shape
    l = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(l,),
        in_specs=[pl.BlockSpec((1, page, h, d),
                               lambda i, idx_ref: (idx_ref[i], 0, 0, 0))],
        out_specs=pl.BlockSpec((1, page, h, d),
                               lambda i, idx_ref: (i, 0, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((l, page, h, d), pool.dtype),
        interpret=interpret,
    )(idx, pool)


# There is deliberately NO Pallas paged_scatter twin: the bulk page plane
# runs *off* the critical path (DaeMon §4.1), and inside the jitted step
# XLA's native scatter already updates the pool buffer in place — a
# donated wrapper or a Pallas kernel buys nothing there (measured; see
# EXPERIMENTS.md "Kernel plane"). The writeback entry is
# ops.paged_scatter -> ref.paged_scatter; the fused transaction kernel
# (residency_fused.py) does its landing scatter via in-kernel DMA. The
# gather above is the critical sub-block plane and is the kernel.