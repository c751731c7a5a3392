"""Mixture-of-Experts: top-k routing, dense oracle + expert-parallel path.

Two implementations, numerically equivalent (tested against each other):

* ``moe_dense`` — every expert runs on every token, masked combine. The
  correctness oracle; used by CPU smoke tests (reduced configs only: it
  wastes E/k FLOPs).
* ``moe_ep`` — production expert parallelism under ``shard_map``: tokens are
  sort-grouped by destination shard (capacity-bounded), exchanged with
  ``all_to_all`` over the `model` axis, sort-grouped again by local expert,
  run through a batched (E_local, C, D) x (E_local, D, F) matmul, and
  returned. This is the DaeMon *sub-block critical plane* of the MoE: token
  dispatch is fine-grained movement that must never stall behind bulk
  (expert weight) traffic — see core/collectives.py for the compressed-link
  variant of the dispatch.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import F32, ParamBuilder, dot, silu
from repro.runtime.mesh_rules import active_mesh, dp_axis_names


def init_moe(key, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pb = ParamBuilder(key)
    pb.add("router", (d, e), (None, None), scale=0.02)
    # experts over `model` (EP) + hidden dim over `data` (FSDP): master
    # weights/optimizer shard 256-way; the shard_map in_spec
    # P("model", None, None) makes XLA all-gather the bf16 working copy
    # over data per layer use (§Perf it8: 16x less optimizer memory for
    # ~25% more wire on the MoE cells)
    pb.add("w_gate", (e, d, f), ("experts", "fsdp", None))
    pb.add("w_up", (e, d, f), ("experts", "fsdp", None))
    pb.add("w_down", (e, f, d), ("experts", "fsdp", None))
    return pb.build()


def _route(params, cfg, x):
    """Returns (weights (B,S,k) f32, idx (B,S,k) i32, aux_loss scalar)."""
    logits = dot(x, params["router"].astype(x.dtype), "bsd,de->bse")
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.experts_per_token
    top_w, top_i = jax.lax.top_k(logits, k)
    top_w = jax.nn.softmax(top_w, axis=-1)              # normalize over k
    # load-balance aux loss (Switch-style): E * sum_e importance_e * load_e
    e = cfg.num_experts
    importance = probs.mean(axis=(0, 1))                # (E,)
    counts = jnp.zeros((e,), F32).at[top_i.reshape(-1)].add(1.0)
    load = counts / top_i.size
    aux = e * jnp.sum(importance * load)
    return top_w, top_i, aux


def moe_dense(params, cfg, x):
    """Oracle: all experts on all tokens, masked combine. (B,S,D)."""
    dtype = x.dtype
    w, idx, aux = _route(params, cfg, x)
    e = cfg.num_experts
    gates = (jax.nn.one_hot(idx, e, dtype=F32) * w[..., None]).sum(-2)
    g = dot(x, params["w_gate"].astype(dtype), "bsd,edf->bsef")
    u = dot(x, params["w_up"].astype(dtype), "bsd,edf->bsef")
    h = (silu(g) * u).astype(dtype)
    y = dot(h, params["w_down"].astype(dtype), "bsef,efd->bsed")
    y = (y * gates[..., None]).sum(axis=2)
    return y.astype(dtype), aux


# --------------------------------------------------------------------------
# expert-parallel (shard_map) path
# --------------------------------------------------------------------------
def _round8(n: int) -> int:
    return max(8, ((n + 7) // 8) * 8)


def _group_by(ids, num_groups: int, capacity: int, payload):
    """Sort-group rows of `payload` by `ids` into (num_groups, capacity, D).

    Returns (buffer, order, dst, keep) so callers can invert the grouping:
    row j of the sorted order landed at flat slot dst[j] (overflow slot
    num_groups*capacity when its group exceeded capacity).
    """
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sids = ids[order]
    first = jnp.searchsorted(sids, jnp.arange(num_groups))
    pos = jnp.arange(n) - first[sids]
    keep = pos < capacity
    dst = jnp.where(keep, sids * capacity + pos, num_groups * capacity)
    buf = jnp.zeros((num_groups * capacity + 1, payload.shape[1]),
                    payload.dtype)
    buf = buf.at[dst].set(payload[order] * keep[:, None].astype(payload.dtype))
    return buf[:-1].reshape(num_groups, capacity, -1), order, dst, keep


def _ungroup(buf_flat, order, dst, keep, n):
    """Inverse of _group_by for a result buffer of the same layout."""
    pad = jnp.concatenate([buf_flat,
                           jnp.zeros((1, buf_flat.shape[1]),
                                     buf_flat.dtype)], 0)
    y_sorted = pad[dst] * keep[:, None].astype(buf_flat.dtype)
    return jnp.zeros((n, buf_flat.shape[1]), buf_flat.dtype
                     ).at[order].set(y_sorted)


def _ep_local(axis_name, e_total, k, cf, xl, idxl, wl, wg, wu, wd):
    """Per-shard EP body (inside shard_map).

    xl (Tl, D) local tokens; idxl (Tl, k) global expert ids; wl (Tl, k).
    wg/wu/wd: (E_local, D, F) / (E_local, F, D) local expert weights.
    """
    # shard count from the static weight shapes (jax.lax.axis_size is a
    # newer-jax spelling, and m must be a python int for reshapes anyway)
    e_local = wg.shape[0]
    m = e_total // e_local
    tl, d = xl.shape
    nslots = tl * k
    slot_expert = idxl.reshape(-1)
    slot_token = jnp.arange(nslots) // k
    dest = slot_expert // e_local

    cs = _round8(int(math.ceil(nslots / m * cf)))
    # payload: features + local expert id + valid flag
    meta = jnp.stack([(slot_expert % e_local).astype(xl.dtype),
                      jnp.ones((nslots,), xl.dtype)], axis=1)
    payload = jnp.concatenate([xl[slot_token], meta], axis=1)
    send, order, dst, keep = _group_by(dest, m, cs, payload)

    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(m * cs, d + 2)
    feats, eid_f, valid = recv[:, :d], recv[:, d], recv[:, d + 1]
    eid = jnp.where(valid > 0.5, eid_f.astype(jnp.int32), e_local)

    ce = _round8(int(math.ceil(m * cs / max(e_local, 1) * cf)))
    buf, order2, dst2, keep2 = _group_by(eid, e_local, ce, feats)
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype),
                   preferred_element_type=F32)
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(buf.dtype),
                   preferred_element_type=F32)
    h = (silu(g) * u).astype(buf.dtype)
    yb = jnp.einsum("ecf,efd->ecd", h, wd.astype(buf.dtype),
                    preferred_element_type=F32).astype(buf.dtype)
    y_recv = _ungroup(yb.reshape(e_local * ce, d), order2, dst2, keep2,
                      m * cs)

    back = jax.lax.all_to_all(y_recv.reshape(m, cs, d), axis_name,
                              split_axis=0, concat_axis=0, tiled=False)
    y_slot = _ungroup(back.reshape(m * cs, d), order, dst, keep, nslots)
    y_tok = (y_slot.reshape(tl, k, d)
             * wl.reshape(tl, k, 1).astype(y_slot.dtype)).sum(axis=1)
    return y_tok


def _token_spec(mesh, t: int, axis_name: str):
    """Token-dim sharding for the EP region: tokens must be *partitioned*
    over the model axis (each device owns a distinct block) so the
    all_to_all is a true exchange. Falls back when t is not divisible."""
    dp = dp_axis_names(mesh)
    for axes in (dp + (axis_name,), (axis_name,)):
        size = math.prod(mesh.shape[a] for a in axes)
        if t % size == 0:
            return axes if len(axes) > 1 else axes[0]
    return None  # caller must use the dense path


def moe_ep(params, cfg, x, axis_name: str = "model"):
    """Expert-parallel MoE over `axis_name`. x: (B,S,D).

    Tokens are re-sharded (sequence-parallel style) over dp x model for the
    dispatch region; XLA inserts the cheap slice on entry and the D-sized
    all-gather on exit (same boundary cost as a TP MLP).
    """
    mesh = active_mesh()
    assert mesh is not None and axis_name in mesh.shape, \
        "moe_ep requires an active mesh with a model axis"
    b, s, d = x.shape
    tspec = _token_spec(mesh, b * s, axis_name)
    if tspec is None:
        return moe_dense(params, cfg, x)
    w, idx, aux = _route(params, cfg, x)
    xf = x.reshape(b * s, d)
    idxf = idx.reshape(b * s, cfg.experts_per_token)
    wf = w.reshape(b * s, cfg.experts_per_token)

    body = partial(_ep_local, axis_name, cfg.num_experts,
                   cfg.experts_per_token, cfg.moe_capacity_factor)
    yf = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tspec, None), P(tspec, None), P(tspec, None),
                  P(axis_name, None, None), P(axis_name, None, None),
                  P(axis_name, None, None)),
        out_specs=P(tspec, None),
    )(xf, idxf, wf, params["w_gate"], params["w_up"], params["w_down"])
    return yf.reshape(b, s, d).astype(x.dtype), aux


def moe(params, cfg, x, impl: str = "dense"):
    if impl == "ep":
        return moe_ep(params, cfg, x)
    return moe_dense(params, cfg, x)
