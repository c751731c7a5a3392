"""Pipeline parallelism (GPipe-style) over a `stage` mesh axis.

Forward pipeline via shard_map + ppermute: each stage holds its layer
block; microbatches stream through with the classic (M + S - 1)-tick
schedule. Off by default on the 2-axis production mesh (DP x TP covers the
assigned cells); enabled for meshes with a "stage" axis and covered by
tests/test_pipeline.py on a 4-stage CPU mesh.

Training-time PP (1F1B with backward scheduling) composes with jax.grad
through this forward (the scan over ticks is differentiable); the
schedule is GPipe (activations of all in-flight microbatches live until
their backward) — documented trade-off vs 1F1B in DESIGN.md.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(mesh, stage_fn, stage_params, x_micro,
                     axis: str = "stage"):
    """Run microbatches through S pipeline stages.

    stage_params: pytree with leading (S, ...) dim (sharded over `axis`);
    x_micro: (M, mb, ...) microbatches (replicated);
    stage_fn(params_slice, x) -> y, same shape as x.
    Returns (M, mb, ...) outputs.
    """
    s = mesh.shape[axis]
    m = x_micro.shape[0]

    def per_stage(params_block, xs):
        # params_block: (1, ...) this stage's params; xs: full (M, mb, ...)
        params_here = jax.tree.map(lambda t: t[0], params_block)
        stage_id = jax.lax.axis_index(axis)
        ticks = m + s - 1
        buf = jnp.zeros_like(xs)          # completed outputs (stage-local)
        cur = jnp.zeros_like(xs[0])
        # carries become device-varying across the stage axis (ppermute);
        # mark the initial values accordingly for the vma checker
        try:
            buf = jax.lax.pvary(buf, (axis,))
            cur = jax.lax.pvary(cur, (axis,))
        except AttributeError:
            pass  # older jax: no varying-manual-axes checker, nothing to mark

        def tick(carry, t):
            cur, buf = carry
            # stage 0 injects microbatch t; others use what arrived
            inject = jnp.where(t < m, t, 0)
            x_in = jnp.where(stage_id == 0, xs[inject], cur)
            active = (t - stage_id >= 0) & (t - stage_id < m)
            y = stage_fn(params_here, x_in)
            y = jnp.where(active, y, cur)
            # last stage records its finished microbatch
            mb_idx = jnp.clip(t - stage_id, 0, m - 1)
            buf = jnp.where(
                active & (stage_id == s - 1),
                jax.lax.dynamic_update_slice_in_dim(
                    buf, y[None], mb_idx, axis=0),
                buf)
            # shift y to the next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return (nxt, buf), None

        (_, buf), _ = jax.lax.scan(tick, (cur, buf), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        out = jax.lax.psum(
            jnp.where(stage_id == s - 1, buf, jnp.zeros_like(buf)), axis)
        return out

    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(P(axis), P()),
                       out_specs=P())
    return fn(stage_params, x_micro)
