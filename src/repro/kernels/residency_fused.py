"""Pallas TPU kernel: the fused residency-engine transaction.

ONE kernel per decode step executes the store's whole local-tier hot
path (DESIGN.md §9): landing compaction, policy-scored victim selection,
dirty-eviction enqueue into the writeback list, landed-page pool scatter,
set-associative CAM probe with the `ready` in-flight gate, hit-path pool
gather, and the policy touch / dirty-bit metadata updates — replacing
the seven-op jnp chain (`daemon_store._land` + `_lookup`). The grid is
the batch: grid step b transacts sequence b's table against the shared
remote tier.

Data placement: table metadata (page/age/ready/dirty/rrpv, (S, W) per
sequence) rides VMEM blocks; the per-sequence request vectors ride
(1, 1, m) VMEM blocks; the KV pools, the remote tier and the per-request
output pages stay in HBM (`pl.ANY`) and move ONLY via per-row async
copies (`pltpu.make_async_copy`) at in-kernel computed slots — landed
pages DMA remote->pool at the victim slot, hits DMA pool->output at the
probe slot, and the pools are aliased in place (`input_output_aliases`)
so untouched rows never move. Replacement policy arrives as traced
`PolicyFlags` data (lru / fifo / rrip / dirty-averse select by
`jnp.where`), never Python branches — the one compiled kernel serves
the whole policy lattice.

Mosaic-safe construction (checked by compiling for a described v5e in
tests/test_chip_compile.py):
  * every block's last two dims are the array's own, and no lane vector
    is ever indexed at a traced position: per-lane scalars (landed
    flags, page ids, request pages) are read out of their vectors by a
    masked full reduction into SMEM scratch, and the landing compaction,
    same-set ranking and every DMA address are scalar code over SMEM;
  * table reads and writes at computed slots are masked select /
    reduce over the (S, W) table, one landing lane or request at a time
    — no gather / scatter / sort primitives, no scalar stores to VMEM;
  * DMA'd rows must be whole (sublane, 128-lane) tiles: a payload row
    that is not (e.g. the (4, 1, 8) rows of the hot-path benchmark) is
    flattened and zero-padded to one outside the kernel (`_dma_view`),
    at the cost of a relayout copy of the pools per call. A latent
    store holds its pages as whole tiles already (a 64-token page of
    576 values is (288, 128)), so its pools, tier and rows move as they
    are.
Victim ordering is stable-rank arithmetic (O(W) passes over the table
per step — the kernel targets set-associative geometries with modest W,
e.g. 256x16). The pure-jnp oracle is `ref.fused_residency_step`;
bit-identity across all four policies is pinned by
tests/test_residency_fused.py (interpret mode — reserved for tests,
never production graphs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import residency

F32 = jnp.float32
I32 = jnp.int32
LANES = 128
KERNEL_NAME = "residency_fused"   # the custom call's name in compiled HLO


def _pick(vec, iota, i):
    """Lane `i` (traced or static) of a small vector as a scalar: a
    masked full reduction, the Mosaic-safe way to move a vector lane to
    the scalar unit."""
    return jnp.sum(jnp.where(iota == i, vec, jnp.zeros_like(vec)))


def _make_kernel(s_sets: int, w_ways: int, p_inflight: int, k_land: int,
                 n_req: int, n_t: int):
    """The kernel over `n_t` page tensors: 2 for a K/V pair, 1 for a
    latent page. Each tensor brings a pool and a remote tier (inputs)
    and its pool and per-request rows (outputs)."""

    def kernel(*refs):
        (fpar_ref, ipar_ref, page_ref, age_ref, ready_ref, dirty_ref,
         rrpv_ref, landed_ref, lpage_ref, need_ref, write_ref) = refs[:11]
        refs = refs[11:]
        # refs[:n_t] are the pools, aliased: read/write via opool_refs
        tier_refs, refs = refs[n_t:2 * n_t], refs[2 * n_t:]
        (opage_ref, oage_ref, oready_ref, odirty_ref, orrpv_ref,
         oevict_ref, onev_ref, ohit_ref) = refs[:8]
        refs = refs[8:]
        opool_refs, local_refs = refs[:n_t], refs[n_t:2 * n_t]
        (s_landed, s_lpage, s_need, s_write, s_pid, s_land, s_vslot,
         s_slot, sems) = refs[2 * n_t:]
        b = pl.program_id(0)
        clock = fpar_ref[0, 0]
        dpen = fpar_ref[0, 1]              # dirty_penalty
        touch_refresh = ipar_ref[0, 0] > 0
        rrip = ipar_ref[0, 1] > 0

        page = page_ref[0]                 # (S, W)
        age = age_ref[0]
        ready = ready_ref[0]
        dirty = dirty_ref[0]               # i32 0/1
        rrpv = rrpv_ref[0]
        tshape = (s_sets, w_ways)
        s_iota = jax.lax.broadcasted_iota(I32, tshape, 0)
        w_iota = jax.lax.broadcasted_iota(I32, tshape, 1)

        # ---- per-lane scalars into SMEM
        p_iota = jax.lax.broadcasted_iota(I32, (1, p_inflight), 1)
        landed_v, lpage_v = landed_ref[0], lpage_ref[0]
        r_iota = jax.lax.broadcasted_iota(I32, (1, n_req), 1)
        need_v, write_v = need_ref[0], write_ref[0]

        def spill_slot(i, carry):
            s_landed[i] = _pick(landed_v, p_iota, i)
            s_lpage[i] = _pick(lpage_v, p_iota, i)
            return carry

        def spill_req(r, carry):
            s_need[r] = _pick(need_v, r_iota, r)
            s_write[r] = _pick(write_v, r_iota, r)
            return carry

        jax.lax.fori_loop(0, p_inflight, spill_slot, 0)
        jax.lax.fori_loop(0, n_req, spill_req, 0)

        # ---- landing compaction: lane j <- j-th landed slot (slot order)
        def compact(i, n):
            is_landed = s_landed[i] > 0

            @pl.when(is_landed & (n < k_land))
            def _():
                s_pid[n] = s_lpage[i]

            return n + is_landed.astype(I32)

        n_landed = jax.lax.fori_loop(0, p_inflight, compact, I32(0))

        # ---- per-set stable eviction rank of every way (== the stable
        # argsort position of residency.evict_order_sets)
        amin = jnp.min(age, axis=1, keepdims=True)
        span = jnp.max(age, axis=1, keepdims=True) - amin + 1.0
        base = age + jnp.where(dirty > 0, dpen * span, 0.0)
        rrs = (residency.RRPV_MAX - rrpv) * span + (age - amin)
        score = jnp.where(rrip, rrs, base)
        rank_w = jnp.zeros(tshape, I32)
        for w2 in range(w_ways):
            c = jnp.sum(jnp.where(w_iota == w2, score, 0.0), axis=1,
                        keepdims=True)             # (S, 1): way w2
            before = (c < score) | ((c == score) & (w2 < w_iota))
            rank_w = rank_w + before.astype(I32)

        # ---- victim assignment + insert: lane j takes its set's rank-j
        # victim (rank = position among earlier same-set lanes)
        opage_ref[0] = page
        oage_ref[0] = age
        oready_ref[0] = ready
        odirty_ref[0] = dirty
        orrpv_ref[0] = rrpv
        k_iota = jax.lax.broadcasted_iota(I32, (1, k_land), 1)

        def land_lane(j, carry):
            ev_row, n_ev = carry
            live = j < n_landed
            pid = jnp.where(live, s_pid[j], 0)
            set_j = jax.lax.rem(jnp.maximum(pid, 0), s_sets)

            def same_set(jj, cnt):   # a live lane's earlier lanes are live
                pj = jnp.maximum(s_pid[jj], 0)
                return cnt + (jax.lax.rem(pj, s_sets) == set_j
                              ).astype(I32)

            rank = jax.lax.fori_loop(0, j, same_set, I32(0))
            do = live & (rank < w_ways)            # same-set overflow
            vmask = (s_iota == set_j) & (rank_w == jnp.minimum(
                rank, w_ways - 1))                 # exactly one slot
            vway = jnp.sum(jnp.where(vmask, w_iota, 0))
            vict_page = jnp.sum(jnp.where(vmask, page, 0))
            vict_dirty = jnp.sum(jnp.where(vmask, dirty, 0)) > 0
            resident = vict_page >= 0
            s_land[j] = jnp.where(do, jnp.maximum(pid, 0), -1)
            s_vslot[j] = set_j * w_ways + vway
            ev = jnp.where(do & vict_dirty & resident, vict_page, -1)
            ins = vmask & do
            opage_ref[0] = jnp.where(ins, pid, opage_ref[0])
            oage_ref[0] = jnp.where(ins, clock, oage_ref[0])
            oready_ref[0] = jnp.where(ins, clock, oready_ref[0])
            odirty_ref[0] = jnp.where(ins, 0, odirty_ref[0])
            orrpv_ref[0] = jnp.where(ins, residency.RRPV_INSERT,
                                     orrpv_ref[0])
            return (jnp.where(k_iota == j, ev, ev_row),
                    n_ev + (do & resident).astype(I32))

        ev_row, n_ev = jax.lax.fori_loop(
            0, k_land, land_lane,
            (jnp.full((1, k_land), -1, I32), I32(0)))
        oevict_ref[0] = ev_row
        onev_ref[0] = jnp.full((1, 1), n_ev, I32).astype(F32)

        # ---- CAM probe (after insert: a page landing this step hits
        # now) + policy touch + dirty propagation on hits
        page2 = opage_ref[0]
        ready2 = oready_ref[0]

        def probe(r, hit_row):
            need = s_need[r]
            set_r = need % s_sets
            match = (page2 == need) & (s_iota == set_r)
            present = jnp.sum(match.astype(I32)) > 0
            way = jnp.where(present,
                            jnp.min(jnp.where(match, w_iota, w_ways)), 0)
            s_slot[r] = set_r * w_ways + way
            smask = (s_iota == set_r) & (w_iota == way)
            ready_ok = jnp.sum((smask & (ready2 <= clock)).astype(I32)) > 0
            hit = present & ready_ok
            refresh = jnp.where(hit & touch_refresh, clock, 0.0)
            oage_ref[0] = jnp.where(smask,
                                    jnp.maximum(oage_ref[0], refresh),
                                    oage_ref[0])
            promote = jnp.where(hit, residency.RRPV_HIT,
                                residency.RRPV_MAX)
            orrpv_ref[0] = jnp.where(smask,
                                     jnp.minimum(orrpv_ref[0], promote),
                                     orrpv_ref[0])
            odirty_ref[0] = jnp.where(smask & hit & (s_write[r] > 0), 1,
                                      odirty_ref[0])
            return jnp.where(r_iota == r, hit.astype(I32), hit_row)

        ohit_ref[0] = jax.lax.fori_loop(0, n_req, probe,
                                        jnp.zeros((1, n_req), I32))

        # ---- data movement: landed pages remote -> pool (victim slots)
        def move(srcs, dsts):
            copies = [pltpu.make_async_copy(src, dst, sems.at[i])
                      for i, (src, dst) in enumerate(zip(srcs, dsts))]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

        def land_body(j, carry):
            src = s_land[j]

            @pl.when(src >= 0)
            def _():
                slot = s_vslot[j]
                move([t.at[src] for t in tier_refs],
                     [o.at[b, slot] for o in opool_refs])

            return carry

        jax.lax.fori_loop(0, k_land, land_body, 0)

        # ---- hit-path gather: pool (post-landing) -> per-request output
        def gather_body(r, carry):
            slot = s_slot[r]
            move([o.at[b, slot] for o in opool_refs],
                 [o.at[b, r] for o in local_refs])
            return carry

        jax.lax.fori_loop(0, n_req, gather_body, 0)

    return kernel


def in_compiled(hlo_text: str) -> bool:
    """Whether compiled HLO text (`compiled.as_text()`) calls this kernel
    as a Mosaic custom call — i.e. the kernel, not its oracle, ran."""
    return any("tpu_custom_call" in line and KERNEL_NAME in line
               for line in hlo_text.splitlines())


def _dma_view(x, lead: int):
    """`x` (*lead, *row) as rows the TPU's DMA engine can address one at
    a time. A row whose last two dims are whole tiles (lanes a multiple
    of 128, sublanes a multiple of the dtype's packing) moves as it is;
    any other row is flattened and zero-padded to (rows, 128) with rows a
    multiple of the packing. Returns (view, undo)."""
    row = x.shape[lead:]
    pack = max(1, 4 // x.dtype.itemsize)
    if len(row) >= 2 and row[-1] % LANES == 0 and row[-2] % pack == 0:
        return x, lambda y: y
    n = math.prod(row)
    rows = -(-n // LANES)
    rows = -(-rows // pack) * pack
    flat = x.reshape(x.shape[:lead] + (n,))
    pad = [(0, 0)] * lead + [(0, rows * LANES - n)]
    view = jnp.pad(flat, pad).reshape(x.shape[:lead] + (rows, LANES))

    def undo(y):
        lead_shape = y.shape[:lead]
        return y.reshape(lead_shape + (rows * LANES,))[..., :n].reshape(
            lead_shape + row)

    return view, undo


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_residency_step(res, kpool, vpool, remote_k, remote_v, landed,
                         landed_pages, needed_pages, needed_writes,
                         clock, pol, *, interpret: bool = False):
    """Batched fused residency transaction — Pallas twin of
    `ref.fused_residency_step` (same signature + `interpret`, same
    returns). Pools and the remote tier must share a dtype (the landing
    DMA is a raw copy; the jnp chain's astype is a no-op there anyway).
    A latent store passes `vpool = remote_v = None` and gets `None` back
    for its v pool and v rows: the kernel then moves one tensor.
    """
    pol = residency.as_policy(pol)
    b, s_sets, w_ways = res.page.shape
    n_slots = s_sets * w_ways
    p_inflight = int(landed.shape[1])
    n_req = int(needed_pages.shape[1])
    k_land = min(p_inflight, n_slots)
    assert (vpool is None) == (remote_v is None)
    pools = [p for p in (kpool, vpool) if p is not None]
    tiers = [t for t in (remote_k, remote_v) if t is not None]
    assert all(t.dtype == p.dtype for t, p in zip(tiers, pools))
    n_t = len(pools)

    views = [_dma_view(p, 2) for p in pools]
    undo_pool = views[0][1]
    pool_vs = [v for v, _ in views]
    tier_vs = [_dma_view(t, 1)[0] for t in tiers]
    row_v = tuple(pool_vs[0].shape[2:])

    fpar = jnp.stack([jnp.asarray(clock, F32),
                      jnp.asarray(pol.dirty_penalty, F32)]).reshape(1, 2)
    ipar = jnp.stack([jnp.asarray(pol.touch_refresh, I32),
                      jnp.asarray(pol.rrip, I32)]).reshape(1, 2)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    meta = pl.BlockSpec((1, s_sets, w_ways), lambda i: (i, 0, 0))
    vec = lambda m: pl.BlockSpec((1, 1, m), lambda i: (i, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    as_vec = lambda x: jnp.asarray(x, I32).reshape(b, 1, -1)
    tbl = lambda dt: jax.ShapeDtypeStruct((b, s_sets, w_ways), dt)

    outs = pl.pallas_call(
        _make_kernel(s_sets, w_ways, p_inflight, k_land, n_req, n_t),
        grid=(b,),
        in_specs=[smem, smem, meta, meta, meta, meta, meta,
                  vec(p_inflight), vec(p_inflight), vec(n_req),
                  vec(n_req)] + [any_spec] * (2 * n_t),
        out_specs=[meta, meta, meta, meta, meta, vec(k_land), vec(1),
                   vec(n_req)] + [any_spec] * (2 * n_t),
        out_shape=[
            tbl(I32), tbl(F32), tbl(F32), tbl(I32), tbl(F32),
            jax.ShapeDtypeStruct((b, 1, k_land), I32),         # evicted
            jax.ShapeDtypeStruct((b, 1, 1), F32),              # n_evict
            jax.ShapeDtypeStruct((b, 1, n_req), I32),          # local_hit
        ] + [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in pool_vs]
        + [jax.ShapeDtypeStruct((b, n_req) + row_v, v.dtype)
           for v in pool_vs],
        scratch_shapes=[
            pltpu.SMEM((p_inflight,), I32),    # landed flag per slot
            pltpu.SMEM((p_inflight,), I32),    # landed page per slot
            pltpu.SMEM((n_req,), I32),         # requested page
            pltpu.SMEM((n_req,), I32),         # request writes
            pltpu.SMEM((k_land,), I32),        # lane's landed page id
            pltpu.SMEM((k_land,), I32),        # lane's DMA source, -1 off
            pltpu.SMEM((k_land,), I32),        # lane's victim slot
            pltpu.SMEM((n_req,), I32),         # request's probe slot
            pltpu.SemaphoreType.DMA((n_t,)),
        ],
        input_output_aliases={11 + i: 8 + i for i in range(n_t)},
        interpret=interpret,
        name=KERNEL_NAME,
    )(fpar, ipar, res.page, res.age, res.ready,
      res.dirty.astype(I32), res.rrpv,
      as_vec(landed), as_vec(landed_pages), as_vec(needed_pages),
      as_vec(needed_writes), *pool_vs, *tier_vs)

    opage, oage, oready, odirty, orrpv, evicted, n_ev, hit = outs[:8]
    opools = [undo_pool(p) for p in outs[8:8 + n_t]]
    locals_ = [undo_pool(x) for x in outs[8 + n_t:]]
    if n_t == 1:
        opools.append(None)
        locals_.append(None)
    res2 = residency.ResidencyState(page=opage, age=oage, ready=oready,
                                    dirty=odirty > 0, rrpv=orrpv)
    return (res2, *opools, evicted[:, 0], n_ev[:, 0, 0], *locals_,
            hit[:, 0] > 0)
