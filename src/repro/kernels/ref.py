"""Pure-jnp oracles for every Pallas kernel (the `ref.py` of kernels/).

These are the ground truth for tests/test_kernels.py; the distributed
graphs on CPU also run these (Pallas lowering needs a real TPU; interpret
mode is for validation only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import residency

F32 = jnp.float32


def quantize_block_int8(x2d):
    """x2d: (N, B) f32 -> (q int8 (N,B), scale f32 (N,1))."""
    amax = jnp.max(jnp.abs(x2d.astype(F32)), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x2d.astype(F32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def dequantize_block_int8(q, scale, dtype=F32):
    return (q.astype(F32) * scale).astype(dtype)


def bdi_compress(x2d_i32, delta_bits: int = 8):
    """x2d: (N, B) int32 -> (base (N,1) i32, deltas (N,B) i8, ok (N,1) i8).

    A row compresses iff every word fits base + int8 delta (BDI-style).
    """
    base = x2d_i32[:, :1]
    delta = x2d_i32.astype(jnp.int64) - base.astype(jnp.int64)
    lim = 2 ** (delta_bits - 1)
    ok = jnp.all((delta >= -lim) & (delta < lim), axis=1, keepdims=True)
    deltas = jnp.clip(delta, -lim, lim - 1).astype(jnp.int8)
    return base, deltas, ok.astype(jnp.int8)


def bdi_decompress(base, deltas, ok, raw):
    """Reconstruct: compressed rows from base+delta, others from raw."""
    rec = (base.astype(jnp.int64) + deltas.astype(jnp.int64)).astype(
        jnp.int32)
    return jnp.where(ok.astype(bool), rec, raw)


def paged_gather(pool, idx):
    """pool: (P, *row); idx: (L,) int32 -> (L, *row).

    The DaeMon critical-path fetch: gather hot KV pages from the pool.
    """
    return pool[idx]


def paged_scatter(pool, idx, pages, *, mode=None):
    """Inverse: write pages back into the pool at idx. mode="drop" makes
    out-of-bounds rows no-ops — the masked-lane convention the fused
    transaction uses (a gated-off lane must never clobber a live one
    that shares its clamped slot)."""
    return pool.at[idx].set(pages, mode=mode)


def fused_residency_step(res, kpool, vpool, remote_k, remote_v, landed,
                         landed_pages, needed_pages, needed_writes, clock,
                         pol):
    """The whole per-step residency transaction, fused — pure-jnp oracle.

    Landing (victim selection + dirty-eviction enqueue + pool scatter of
    the arrived remote pages) followed by the CAM lookup (probe with the
    `ready` in-flight gate, hit-path pool gather, policy touch + dirty
    propagation) — the store's `_land` + `_lookup` arithmetic as ONE op.
    Composed from the same residency primitives the legacy chain uses,
    so it is bit-identical to the chain by construction (pinned by
    tests/test_residency_fused.py); `residency_fused.fused_residency_step`
    is the Pallas kernel validated against this.

    Batched: `res` leaves (B, S, W); kpool/vpool (B, N, *row) with
    N = S*W flat slots (row = (page, KV, D) for a K/V pair); landed/
    landed_pages (B, P) from `poll_arrivals`; needed_pages/needed_writes
    (B, R); remote_k/remote_v (PR, *row) shared across the batch; `clock`
    scalar; `pol` traced PolicyFlags. A latent store has one page tensor:
    it passes `vpool = remote_v = None` and gets None for v back.

    Returns (res', kpool', vpool', evicted (B, k) i32 dirty-evicted page
    ids (-1 pad), n_evictions (B,) f32, k_local/v_local (B, R, *row),
    local_hit (B, R) bool). More than W same-set landings on one step
    drop the overflow (the >N-landings rule); at S=1 this cannot happen.
    """
    pol = residency.as_policy(pol)
    assert (vpool is None) == (remote_v is None)
    tiers = (remote_k, remote_v)

    def one(res, kpool, vpool, landed, lpages, needed, writes):
        s_sets, w_ways = res.page.shape
        n = s_sets * w_ways
        k_land = min(int(landed.shape[0]), n)
        no_evict = jnp.full((k_land,), -1, jnp.int32)

        def do_land(args):
            res, pools = args
            order = jnp.argsort(jnp.logical_not(landed).astype(jnp.int32),
                                stable=True)
            pick = order[:k_land]
            do = landed[pick]
            pids = lpages[pick]
            sets, vways, ok = residency.landing_victims(res, pids, pol)
            do = do & ok
            vict_page = res.page[sets, vways]
            resident = vict_page >= 0
            evicted = jnp.where(do & res.dirty[sets, vways] & resident,
                                vict_page, no_evict)
            n_ev = jnp.sum(do & resident).astype(F32)
            vslot = jnp.where(do, sets * w_ways + vways, n)
            # a None v (the latent layout) is an empty subtree: skipped
            pools = jax.tree.map(
                lambda pool, tier: paged_scatter(
                    pool, vslot,
                    paged_gather(tier, jnp.maximum(pids, 0)).astype(
                        pool.dtype), mode="drop"), pools, tiers)
            res = residency.insert(res, sets, vways, pids, now=clock,
                                   ready=clock, dirty=False, gate=do)
            return (res, pools), evicted, n_ev

        (res, (kpool, vpool)), evicted, n_ev = jax.lax.cond(
            jnp.any(landed), do_land,
            lambda args: (args, no_evict, jnp.zeros((), F32)),
            (res, (kpool, vpool)))

        present, set_idx, way, ready_ok = residency.lookup(res, needed,
                                                           clock)
        local_hit = present & ready_ok
        slot = set_idx * w_ways + way
        k_local, v_local = jax.tree.map(
            lambda pool: paged_gather(pool, jnp.maximum(slot, 0)),
            (kpool, vpool))
        res = residency.touch(res, set_idx, way, clock, pol,
                              gate=local_hit)
        res = residency.mark_dirty(res, set_idx, way, writes,
                                   gate=local_hit)
        return res, kpool, vpool, evicted, n_ev, k_local, v_local, \
            local_hit

    return jax.vmap(one)(res, kpool, vpool, landed, landed_pages,
                         jnp.asarray(needed_pages, jnp.int32),
                         jnp.asarray(needed_writes, bool))


def decode_attention_paged(q, kpages, vpages, page_table, lengths):
    """Paged flash-decode oracle.

    q: (B, NH, D); kpages/vpages: (P, page, KV, D) pool;
    page_table: (B, MAXP) int32 page ids (-1 pad); lengths: (B,) tokens.
    Returns (B, NH, D). KV heads broadcast to NH.
    """
    b, nh, d = q.shape
    p, page, kvh, _ = kpages.shape
    maxp = page_table.shape[1]
    group = nh // kvh
    tbl = jnp.maximum(page_table, 0)
    k = kpages[tbl]                        # (B, MAXP, page, KV, D)
    v = vpages[tbl]
    k = k.reshape(b, maxp * page, kvh, d)
    v = v.reshape(b, maxp * page, kvh, d)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bnd,btnd->bnt", q.astype(F32), k.astype(F32))
    s = s / jnp.sqrt(jnp.asarray(d, F32))
    pos = jnp.arange(maxp * page)
    mask = pos[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnt,btnd->bnd", w, v.astype(F32)).astype(q.dtype)
