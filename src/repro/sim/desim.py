"""Request-level discrete-event simulator of a fully disaggregated system.

The paper evaluates with a heavily modified Sniper; the reproducible
equivalent on a CPU-only box is a request-level DES replaying LLC-miss
traces through: local memory (the shared residency plane,
``repro.core.residency``: one set-associative page table per compute
unit with policy-scored eviction — LRU / FIFO / RRIP / dirty-averse from
the traceable ``residency.POLICIES`` registry, the same tier arithmetic
the serving store's pool runs on), the DaeMon engines
(inflight buffers + selection unit from ``repro.core.engine``), and the
shared movement fabric (``repro.core.fabric``): per-module partitioned
virtual channels over the network and the remote-memory bus — each
service call delegating to ``repro.core.bandwidth.serve_dual``, the only
place channel arithmetic lives — plus page->module placement
(``fabric.place``, the only home of module routing), link compression,
and an MLP-window core model. Network variability is a property of the
fabric's ``LinkModel`` (per-module piecewise time-varying bandwidth
multipliers + health masks, sampled at each request's issue time), not a
hand-threaded per-request array; ``make_net`` attaches a schedule
(``repro.sim.workloads.make_link_schedule`` profiles) and a constant
schedule is bit-identical to a scalar bandwidth. The serving KV store
(``repro.core.daemon_store``) consumes the SAME fabric bank and link
model, so simulator and store cannot diverge on routing, channel
arithmetic, or variability semantics.

The compute side is the mirror substrate (``repro.core.compute_plane``):
``SimConfig.num_cu`` sizes a per-unit envelope — each compute unit owns
its MLP ring, its local page table, its DaeMon engines, and a NIC channel
bank (line/page/writeback busy-until clocks, one set per unit) — while
the shared module banks stay the contention point all units meet at.
Requests shard into per-unit streams over the shared footprint by page
hash (``compute_plane.shard_unit``); every network transfer is priced on
TWO legs — the shared module's channel AND the requesting unit's NIC —
with arrival = the later completion. The number of *active* units is
traced data (an `active_cu` lattice axis, like the link-profile knots),
and the NIC leg is gated off when only one unit is active, so the
``num_cu=1`` path is bit-identical to the pre-compute-plane seed golden.

Scheme flags are *traced data* (``repro.sim.schemes.TraceableFlags``), not
static Python: every scheme switch in the per-request transition is a
``where`` — including the static-vs-adaptive §4.1 repartitioning switch
(the partition ratio is carried per-module state in the fabric, updated by
``bandwidth.adapt_ratio`` only when the `adaptive` flag is set) — so
``simulate_lattice`` runs the whole scheme x network x bw-ratio x
link-profile x compute-unit x replacement-policy lattice as ONE compiled
program ``vmap``ped over every axis — one jit trace per (trace shape,
footprint, SimConfig, schedule knot count, active-C count, policy count)
instead of one per scheme, profile, unit count, or policy.
``simulate_grid`` is the single-scheme wrapper kept for paired
baseline/variant comparisons. Replacement policies are
``residency.PolicyFlags`` pytrees (``simulate_lattice(policies=...)``);
``SimConfig.fifo`` survives only as a deprecated alias for the default
policy (``fifo=True`` == ``policies=[POLICIES['fifo']]``, pinned).

Fidelity notes (vs the paper's cycle-accurate setup) are in DESIGN.md.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (bandwidth, compute_plane, fabric, residency,
                        telemetry)
from repro.core.engine import (EngineState, gate_tree as _gate_tree,
                               init_engine_state, find, retire_arrivals,
                               schedule_line, schedule_page,
                               select_granularity, utilization)
from repro.core.params import DaemonParams, NetworkParams
from repro.core.residency import POLICIES, ResidencyState
from repro.sim.schemes import SchemeFlags, as_traceable, stack_flags
from repro.sim.trace import Trace

F32 = jnp.float32
BIG = jnp.float32(3.0e38)
WAYS = 8
MLP_W = 16


@dataclass(frozen=True)
class SimConfig:
    daemon: DaemonParams = DaemonParams()
    local_frac: float = 0.20      # local memory holds ~20% of the footprint
    # DEPRECATED: alias for the residency plane's policy registry — maps
    # to POLICIES["fifo"] / POLICIES["lru"] when no explicit policy is
    # given (`default_policy`). New callers pass `policies=` to
    # `simulate_lattice` / `policy=` to `run_trace` instead; equivalence
    # is pinned by tests/test_residency.py.
    fifo: bool = False
    num_mc: int = 1               # memory components (fig 17/22)
    mlp: int = MLP_W
    placement: str = "interleave"  # page->module policy (fabric.PLACEMENTS)
    # compute-unit ENVELOPE (fig 22): sizes the per-unit state arrays
    # (rings, tables, engines, NIC banks). How many units actually
    # receive requests is traced data — `simulate_lattice(active_cus=)`
    # — so one envelope compiles once for every C <= num_cu point.
    num_cu: int = 1

    def fabric_config(self) -> fabric.FabricConfig:
        return fabric.FabricConfig(num_modules=self.num_mc,
                                   placement=self.placement)

    def compute_config(self) -> compute_plane.ComputePlaneConfig:
        return compute_plane.ComputePlaneConfig(num_units=self.num_cu)

    def default_policy(self) -> residency.PolicySpec:
        """The `SimConfig.fifo` alias mapping (deprecation shim)."""
        return POLICIES["fifo" if self.fifo else "lru"]


class SimState(NamedTuple):
    """Per-compute-unit leaves carry a leading (C,) axis (C = num_cu);
    `net`/`mem` are the shared per-module banks all units contend on;
    `nic` is the compute-side per-unit channel bank."""
    t: jnp.ndarray               # (C,) per-unit core clock
    ring: jnp.ndarray            # (C, W) outstanding completions per unit
    res: ResidencyState          # local-memory tier, leaves (C, SETS, WAYS)
    eng: EngineState             # leaves (C, ...): one engine per unit
    net: fabric.FabricState      # network-link channel bank (M modules)
    mem: fabric.FabricState      # remote-memory bus channel bank
    nic: fabric.FabricState      # compute-side NIC bank (C units)
    stats: dict
    # telemetry plane (DESIGN.md §10): None below level="counters" — a
    # leafless pytree, so the off path compiles to the same program as
    # before the telemetry plane existed (bit-identity is structural)
    tel: telemetry.TelemetryState = None


STAT_KEYS = ("i", "n", "hits", "lat_sum", "pages_moved", "lines_moved",
             "net_bytes", "wb_bytes", "served_line", "served_page",
             "page_drops", "dirty_evicts", "evictions")

# per-request series channels the telemetry ring samples (at the touched
# module / requesting unit, plus the running stats ratios)
SERIES_CHANNELS = ("page_backlog_ns", "ratio", "hit_rate", "evictions",
                   "wb_bytes", "health")

# `telemetry=None` normalizes to this STATIC off config, so the
# telemetry-off lattice and the pre-telemetry call sites share one jit
# cache entry (the compile-count pins rely on this)
_TEL_OFF = telemetry.TelemetryConfig()


def _net_link(net) -> fabric.LinkModel:
    """The network-side LinkModel carried by a net dict (see `make_net`)."""
    return fabric.LinkModel(bw=jnp.asarray(net["bw"], F32),
                            sched_t=jnp.asarray(net["sched_t"], F32),
                            sched_mult=jnp.asarray(net["sched_mult"], F32),
                            health=jnp.asarray(net["sched_health"], F32))


def _init_state(cfg: SimConfig, n_pages: int, net, ratio0,
                telcfg: telemetry.TelemetryConfig = None) -> SimState:
    sets = residency.geometry(n_pages, cfg.local_frac, WAYS)
    c = cfg.num_cu
    fcfg = cfg.fabric_config()
    # the remote-memory bus is a constant link (the paper's variability
    # axis is the network); it still carries its own adapted ratio
    net_link = _net_link(net)
    mem_link = fabric.constant_link(jnp.asarray(net["membw"], F32),
                                    cfg.num_mc)
    return SimState(
        t=jnp.zeros((c,), F32),
        ring=jnp.zeros((c, cfg.mlp), F32),
        res=compute_plane.replicate(residency.init_residency(sets, WAYS),
                                    c),
        eng=compute_plane.replicate(init_engine_state(cfg.daemon), c),
        net=fabric.init_fabric(fcfg, link=net_link, ratio=ratio0),
        mem=fabric.init_fabric(fcfg, link=mem_link, ratio=ratio0),
        nic=compute_plane.init_nic_bank(
            c, link=compute_plane.nic_link_for(net_link, c), ratio=ratio0),
        stats={k: jnp.zeros((), F32) for k in STAT_KEYS},
        tel=telemetry.init_state(telcfg, len(SERIES_CHANNELS)),
    )


def make_step(flags, cfg: SimConfig, net, comp_ratio, warm_after,
              active_cu=1, policy=None,
              telcfg: telemetry.TelemetryConfig = None):
    """Per-request transition. `flags` may be a SchemeFlags (converted) or
    a TraceableFlags pytree — possibly traced, so every scheme switch
    below is `where`-gated and one compiled step serves any scheme. `net`
    (latencies; the link itself rides in the fabric state), `comp_ratio`,
    `warm_after`, `active_cu` (how many of the `cfg.num_cu` envelope
    units receive requests — the compute-scaling lattice axis) and
    `policy` (a `residency` replacement policy — PolicyFlags pytree,
    PolicySpec, or name; defaults to the `SimConfig.fifo` alias) are
    closed over — traced per lattice point, never broadcast per
    request."""
    fl = as_traceable(flags)
    pol = residency.as_policy(cfg.default_policy() if policy is None
                              else policy)
    dp = cfg.daemon
    comp_lat = dp.compress_latency_ns
    line_b = float(dp.line_bytes)
    page_b = float(dp.page_bytes)
    lpp = dp.lines_per_page
    fcfg = cfg.fabric_config()
    membw = jnp.asarray(net["membw"], F32)
    local_lat = jnp.asarray(net["local_lat"], F32)
    remote_lat = jnp.asarray(net["remote_lat"], F32)
    trans_lat = jnp.asarray(net["trans_lat"], F32)
    switch = jnp.asarray(net["switch"], F32)
    warm_after = jnp.asarray(warm_after, F32)
    comp_ratio = jnp.asarray(comp_ratio, F32)
    active_cu = jnp.asarray(active_cu, jnp.int32)

    def step(st: SimState, inp):
        page, off, gap, wr = inp
        with jax.named_scope("desim.engine"):
            want_page = (fl.move_pages | fl.page_free) & fl.use_local_mem

        # ---- compute-unit sharding (page-hash -> per-unit streams over
        # the shared footprint; active_cu == 1 routes all to unit 0) ----
        with jax.named_scope("desim.issue"):
            cu = compute_plane.shard_unit(page, active_cu)
            nic_on = active_cu > 1            # NIC leg gate (idle at C=1)
        with jax.named_scope("desim.unit_state"):
            ring_u = st.ring[cu]
            res_u = compute_plane.unit_slice(st.res, cu)
            eng = compute_plane.unit_slice(st.eng, cu)

        # ---- core issue (MLP window, per-unit clock + ring) ----
        with jax.named_scope("desim.issue"):
            oldest = jnp.min(ring_u)
            slot = jnp.argmin(ring_u)
            t_issue = jnp.maximum(st.t[cu] + gap, oldest)

        # ---- local memory lookup (the unit's own residency tier) ----
        with jax.named_scope("desim.lookup"):
            set_idx = residency.set_index(res_u, page)
            present, way, ready_ok = residency.lookup_one(res_u, set_idx,
                                                          page, t_issue)
            is_hit = (present & ready_ok & fl.use_local_mem) | fl.local_only
            inflight_tbl = present & ~ready_ok

            eng = retire_arrivals(eng, t_issue, lpp)

        # ---- engine decision (§4.2) ----
        with jax.named_scope("desim.engine"):
            send_line, send_page = select_granularity(
                eng, page, t_issue, selection_enabled=fl.selection,
                always_both=~fl.selection)
            page_found, pidx = find(eng.page_key, page)
            pending_arrival = jnp.where(page_found, eng.page_arrival[pidx],
                                        BIG)
            send_page = (send_page & want_page & ~is_hit & ~inflight_tbl
                         & ~fl.local_only)
            send_line = send_line & fl.move_lines & ~is_hit
            # line-only: always fetch
            line_only = ~fl.move_pages & ~fl.page_free
            send_line = (jnp.where(line_only, ~is_hit, send_line)
                         & ~fl.local_only)

        with jax.named_scope("desim.fabric"):
            mc = fabric.place(fcfg, page)
            sw = switch[mc]
            t0 = t_issue + sw + trans_lat + remote_lat

            # ---- adaptive §4.1 repartitioning (controller before
            # service: each fabric's carried per-module ratio is nudged
            # toward its own observed backlog + the engines' buffer
            # occupancies; `where`-gated on the traceable adaptive flag,
            # so static schemes carry their seed ratio bit-identically) ----
            # floored like occupy_busy's divide: a health-0 (hard-failed)
            # segment must yield huge-but-finite latencies, not inf/NaN
            bw = jnp.maximum(fabric.link_bw_at(st.net.link, mc, t_issue),
                             1e-6)
            sb_occ = utilization(eng.sb_key)
            pg_occ = utilization(eng.page_key)
            wire_b = jnp.where(fl.compress, page_b / comp_ratio, page_b)
            net_fab = fabric.adapt_ratio_at(
                st.net, mc, t_issue, adaptive=fl.adaptive,
                r_idle=fl.bw_ratio, page_unit=wire_b,
                line_occ=sb_occ, page_occ=pg_occ)
            mem_fab = fabric.adapt_ratio_at(
                st.mem, mc, t_issue, adaptive=fl.adaptive,
                r_idle=fl.bw_ratio, page_unit=page_b,
                line_occ=sb_occ, page_occ=pg_occ)
            ratio = net_fab.ratio[mc]
            line_share, page_share = bandwidth.shares(fl.partition, ratio)
            mem_line_share, _ = bandwidth.shares(fl.partition,
                                                 mem_fab.ratio[mc])

            comp_delay = jnp.where(fl.compress, comp_lat, 0.0)
            move_page_physically = send_page & ~fl.page_free

            # ---- remote-memory bus then network link: each a
            # dual-granularity channel bank on the shared fabric
            # (partitioned virtual channels or one shared FIFO per module,
            # at the LinkModel bandwidth sampled at this request's issue
            # time). The network leg is priced on TWO endpoints — the
            # shared module bank and the requesting unit's NIC bank
            # (arrival = later completion); the NIC leg idles when only
            # one unit is active (bit-identical seed path) ----
            mem_fab, lm_done, pm_done = fabric.serve_dual_at(
                mem_fab, mc, partition=fl.partition, now=t_issue,
                line_ready=t0, line_bytes=line_b, line_gate=send_line,
                page_ready=t0, page_bytes=page_b,
                page_gate=move_page_physically)
            (net_fab, nic_fab, ln_done, pn_done, _,
             pn_done_mod) = compute_plane.serve_dual_two_leg(
                net_fab, st.nic, mc, cu, partition=fl.partition,
                now=t_issue,
                line_ready=lm_done, line_bytes=line_b, line_gate=send_line,
                page_ready=pm_done + comp_delay, page_bytes=wire_b,
                page_gate=move_page_physically, active=nic_on)
            line_arrival = jnp.where(send_line, ln_done + sw, BIG)
            # "issued" (left the page queue) = network transmission start
            # on the MODULE channel — until then a later line request can
            # still win the race (§4.2)
            pn_start = pn_done_mod - wire_b / jnp.maximum(bw * page_share,
                                                          1e-6)
            page_arrival = jnp.where(move_page_physically,
                                     pn_done + sw + comp_delay, BIG)
            # page-free: materializes at the cost of one line-granularity
            # access
            free_t = (t_issue + 2 * sw + trans_lat
                      + remote_lat + line_b / bw + line_b / membw)
            page_arrival = jnp.where(fl.page_free & send_page, free_t,
                                     page_arrival)

            # ---- serve time ----
            cand = jnp.minimum(jnp.minimum(line_arrival, page_arrival),
                               pending_arrival)
            untracked = (t_issue + 2 * sw + trans_lat
                         + remote_lat + line_b / (bw * line_share)
                         + line_b / (membw * mem_line_share))
            cand = jnp.where(cand >= BIG / 2, untracked, cand)
            done = jnp.where(is_hit, t_issue + local_lat, cand)

        # ---- engine bookkeeping (gated insertions) ----
        with jax.named_scope("desim.engine"):
            eng = _gate_tree(send_page, eng,
                             schedule_page(eng, page, pn_start, page_arrival))
            eng = _gate_tree(send_line & fl.move_lines, eng,
                             schedule_line(eng, page, off, line_arrival, lpp))

        # ---- residency update (insert page at the policy's victim in
        # the unit's OWN tier; writeback priced on both endpoints) ----
        with jax.named_scope("desim.residency"):
            do_insert = send_page & fl.use_local_mem
            victim = residency.evict_victim(res_u, set_idx, pol)
            evict_page = res_u.page[set_idx, victim]
            evict_dirty = res_u.dirty[set_idx, victim] & (evict_page >= 0)
            wb = do_insert & evict_dirty
            wb_bytes = jnp.where(wb, wire_b, 0.0)
            net_fab, nic_fab, _ = compute_plane.serve_writeback_two_leg(
                net_fab, nic_fab, mc, cu, t_issue, wire_b, gate=wb,
                active=nic_on)

            res_u = residency.insert(res_u, set_idx, victim, page,
                                     now=t_issue, ready=page_arrival,
                                     dirty=wr, gate=do_insert)
            res_u = residency.touch(res_u, set_idx, way, t_issue, pol,
                                    gate=is_hit & present)
            res_u = residency.mark_dirty(res_u, set_idx, way, wr,
                                         gate=is_hit & present)

        # ---- stats (warmup-gated: first `warm_after` requests excluded
        # from latency/hit accounting; total_time still covers the run) ----
        with jax.named_scope("desim.stats"):
            warm = st.stats["i"] >= warm_after
            lat = jnp.where(warm, done - t_issue, 0.0)
            served_line = (~is_hit) & (line_arrival <= jnp.minimum(
                page_arrival, pending_arrival))
            # paper's fig-10 metric: tag-present accesses count as
            # local-memory hits (burst followers of an inflight page are
            # served from local memory once it lands); the triggering first
            # touch is a miss. Latency accounting is unaffected.
            stat_hit = is_hit | inflight_tbl
            stt = st.stats
            stats = {
                "i": stt["i"] + 1.0,
                "n": stt["n"] + warm,
                "hits": stt["hits"] + (stat_hit & warm),
                "lat_sum": stt["lat_sum"] + lat,
                "pages_moved": stt["pages_moved"] + move_page_physically,
                "lines_moved": stt["lines_moved"] + send_line,
                "net_bytes": stt["net_bytes"] + wb_bytes
                + jnp.where(move_page_physically, wire_b, 0.0)
                + jnp.where(send_line, line_b, 0.0),
                "wb_bytes": stt["wb_bytes"] + wb_bytes,
                "served_line": stt["served_line"] + served_line,
                "served_page": stt["served_page"]
                + ((~is_hit) & ~served_line),
                "page_drops": stt["page_drops"] + (
                    (~is_hit) & ~send_page & ~page_found & ~inflight_tbl
                    & want_page),
                "dirty_evicts": stt["dirty_evicts"] + wb,
                "evictions": stt["evictions"]
                + (do_insert & (evict_page >= 0)),
            }

            # ---- telemetry plane (static level axis; None-transparent) ----
            tel = st.tel
            if telcfg is not None and telcfg.enabled:
                # warm-gated end-to-end access latency (hit OR miss) — the
                # same population `lat_sum`/`n` average, as a distribution
                tel = telemetry.record_latency(tel, telcfg, done - t_issue,
                                               gate=warm)
                tel = telemetry.record_series(
                    tel, telcfg, stt["i"].astype(jnp.int32),
                    jnp.stack([
                        fabric.backlog(net_fab, mc, t_issue)[1],
                        ratio,
                        stats["hits"] / jnp.maximum(stats["n"], 1.0),
                        stats["evictions"],
                        stats["wb_bytes"],
                        jnp.mean(fabric.module_health(net_fab.link,
                                                      t_issue)),
                    ]))

        with jax.named_scope("desim.unit_state"):
            new_st = SimState(
                t=st.t.at[cu].set(t_issue),
                ring=st.ring.at[cu, slot].set(done),
                res=compute_plane.unit_update(st.res, cu, res_u),
                eng=compute_plane.unit_update(st.eng, cu, eng),
                net=net_fab, mem=mem_fab, nic=nic_fab,
                stats=stats, tel=tel,
            )
        return new_st, done

    return step


def _simulate_point(cfg, n_pages, telcfg, flags, warm_after, trace_arrays,
                    net, comp_ratio, active_cu, policy):
    """One (scheme, net, active-C, policy) lattice point on pure arrays —
    the vmap kernel. `active_cu` is traced (<= cfg.num_cu envelope);
    `policy` is a traced residency.PolicyFlags pytree; `telcfg` is
    STATIC (the telemetry level axis)."""
    ratio0 = as_traceable(flags).bw_ratio
    st = _init_state(cfg, n_pages, net, ratio0, telcfg)
    step = make_step(flags, cfg, net, comp_ratio, warm_after, active_cu,
                     policy, telcfg)
    final, _ = jax.lax.scan(step, st, trace_arrays)
    total_time = jnp.maximum(jnp.max(final.ring), jnp.max(final.t))
    s = final.stats
    misses = jnp.maximum(s["n"] - s["hits"], 1.0)
    out = {
        "total_time_ns": total_time,
        "avg_miss_ns": s["lat_sum"] / misses,
        "avg_access_ns": s["lat_sum"] / jnp.maximum(s["n"], 1.0),
        "hit_ratio": s["hits"] / jnp.maximum(s["n"], 1.0),
        "pages_moved": s["pages_moved"],
        "lines_moved": s["lines_moved"],
        "net_bytes": s["net_bytes"],
        "page_drops": s["page_drops"],
        "bw_util": s["net_bytes"] / jnp.maximum(
            total_time * net["bw"][0], 1e-6),
    }
    if telcfg is not None and telcfg.histogram_on:
        # in-lattice tail read: the warm-gated access-latency histogram
        # carried through the scan, one CDF walk per cell (under vmap)
        p50, p95, p99 = telemetry.approx_percentiles(
            final.tel.hist, final.tel.edges, [0.5, 0.95, 0.99])
        out["p50_access_ns"] = p50
        out["p95_access_ns"] = p95
        out["p99_access_ns"] = p99
    return out


@partial(jax.jit, static_argnums=(0, 1, 2))
def _lattice_jit(cfg, n_pages, telcfg, tflags, warm_after, trace_arrays,
                 nets, comp_ratio, active_cus, policies):
    """vmap(schemes) o vmap(nets) o vmap(active-C) o vmap(policies) over
    `_simulate_point`, jitted once per (SimConfig, footprint, trace
    shape, schedule knot count, C-sweep length, policy count,
    TelemetryConfig)."""
    point = partial(_simulate_point, cfg, n_pages, telcfg)
    over_pols = jax.vmap(point, in_axes=(None, None, None, None, None,
                                         None, 0))
    over_cus = jax.vmap(over_pols, in_axes=(None, None, None, None, None,
                                            0, None))
    over_nets = jax.vmap(over_cus, in_axes=(None, None, None, 0, None,
                                            None, None))
    over_schemes = jax.vmap(over_nets, in_axes=(0, None, None, None, 0,
                                                None, None))
    return over_schemes(tflags, warm_after, trace_arrays, nets, comp_ratio,
                        active_cus, policies)


def lattice_cache_size() -> int:
    """Compiled lattice variants so far (keyed by SimConfig + shapes)."""
    return _lattice_jit._cache_size()


def _lattice_inputs(schemes, cfg, trace, nets, comp_ratio, warm_frac,
                    active_cus, policies, telemetry_cfg):
    """Validate + array-ify one lattice sweep's inputs.

    Shared by `simulate_lattice` (single-device vmap) and
    `repro.runtime.mesh_plane.simulate_lattice_sharded` (shard_map over
    the nets x policies product) so both paths trace the SAME
    `_simulate_point` on bit-identical operands. Returns
    (tflags, warm_after, arrays, stacked_nets, cr, cus_arr, pols_arr,
    telcfg, squeeze_cu, squeeze_pol, n_cus, n_pols)."""
    schemes = list(schemes)
    if not schemes:
        raise ValueError("simulate_lattice needs at least one scheme")
    squeeze_cu = active_cus is None
    cus = [cfg.num_cu] if squeeze_cu else list(active_cus)
    if not cus or any(c < 1 or c > cfg.num_cu for c in cus):
        raise ValueError(f"active_cus must be a non-empty sequence "
                         f"within [1, num_cu={cfg.num_cu}], got {cus}")
    squeeze_pol = policies is None
    pols = [cfg.default_policy()] if squeeze_pol else list(policies)
    if not pols:
        raise ValueError("simulate_lattice needs at least one policy")
    r = len(trace.page)
    arrays = (jnp.asarray(trace.page), jnp.asarray(trace.off),
              jnp.asarray(trace.gap), jnp.asarray(trace.wr))
    stacked = {k: jnp.stack([jnp.asarray(n[k], F32) for n in nets])
               for k in nets[0]}
    cr = jnp.broadcast_to(jnp.asarray(comp_ratio, F32), (len(schemes),))
    telcfg = _TEL_OFF if telemetry_cfg is None else telemetry_cfg
    # warm_after computed in python float64 (f32(warm_frac) * r can round
    # up past the integer boundary and drop the boundary request)
    return (stack_flags(schemes), jnp.asarray(warm_frac * r, F32),
            arrays, stacked, cr, jnp.asarray(cus, jnp.int32),
            residency.stack_policies(pols), telcfg,
            squeeze_cu, squeeze_pol, len(cus), len(pols))


def _nest_lattice(res, n_schemes, n_nets, n_cus, n_pols,
                  squeeze_cu, squeeze_pol):
    """(S, N, C, P)-leaved metrics dict -> the documented python nesting:
    [scheme][net] -> dict, with [c] / [policy] levels appended when their
    axes were requested (squeezed single-entry axes collapse away)."""
    def cell(i, j, c, p):
        return {k: float(v[i, j, c, p]) for k, v in res.items()}

    def nest(i, j):
        if squeeze_cu and squeeze_pol:
            return cell(i, j, 0, 0)
        if squeeze_pol:
            return [cell(i, j, c, 0) for c in range(n_cus)]
        if squeeze_cu:
            return [cell(i, j, 0, p) for p in range(n_pols)]
        return [[cell(i, j, c, p) for p in range(n_pols)]
                for c in range(n_cus)]

    return [[nest(i, j) for j in range(n_nets)]
            for i in range(n_schemes)]


def simulate_lattice(schemes, cfg: SimConfig, trace: Trace, nets,
                     comp_ratio, warm_frac: float = 0.3,
                     active_cus=None, policies=None,
                     telemetry_cfg: telemetry.TelemetryConfig = None):
    """Every scheme x every net (x every compute-unit count x every
    replacement policy) over one trace in ONE compiled program.

    schemes: sequence of SchemeFlags / TraceableFlags — bw-ratio and
    adaptive variants are just more entries on the scheme axis.
    nets: `make_net` dicts — link-schedule profiles (burst / degradation /
    flap, see `repro.sim.workloads.make_link_schedule`) are just more
    entries on the net axis, provided they share a knot count.
    comp_ratio: scalar or one value per scheme.
    active_cus: optional sequence of active compute-unit counts (each
    <= cfg.num_cu, the static envelope) — the fig-22 compute-scaling
    axis. Counts are traced DATA (request->unit sharding + NIC gating),
    so a {1,2,4,8} sweep rides one compiled program like the link
    profiles do.
    policies: optional sequence of residency replacement policies
    (PolicySpec / PolicyFlags / names from `residency.POLICIES`) — the
    fig-16 local-memory axis. Policy flags are traced DATA (victim
    scoring and hit-refresh are `where`-selected), so an LRU / FIFO /
    RRIP / dirty-averse sweep rides the same compiled program too. None
    (default) runs the single `SimConfig.fifo`-aliased policy squeezed.
    telemetry_cfg: optional STATIC `telemetry.TelemetryConfig` — at
    level "histogram"+ every cell's metrics gain warm-gated
    `p50/p95/p99_access_ns` read from the in-lattice latency histogram
    (DESIGN.md §10). None == level "off": bit-identical outputs and the
    SAME jit cache entry as a pre-telemetry call (compile-count pinned).

    Result nesting: [scheme][net] -> metrics dict of floats, with a [c]
    level appended when `active_cus` is given and a [policy] level
    appended when `policies` is given ([scheme][net][c][policy] with
    both). The jit trace is cached per (SimConfig, footprint, trace
    shape, knot count, C-sweep length, policy count), so repeated
    sweeps — more ratios, networks, profiles, unit counts, or policies —
    cost compile time once.
    """
    schemes = list(schemes)      # may be a generator: list ONCE
    (tflags, warm_after, arrays, stacked, cr, cus_arr, pols_arr, telcfg,
     squeeze_cu, squeeze_pol, n_cus, n_pols) = _lattice_inputs(
        schemes, cfg, trace, nets, comp_ratio, warm_frac, active_cus,
        policies, telemetry_cfg)
    res = _lattice_jit(cfg, trace.n_pages, telcfg, tflags, warm_after,
                       arrays, stacked, cr, cus_arr, pols_arr)
    return _nest_lattice(res, len(schemes), len(nets), n_cus,
                         n_pols, squeeze_cu, squeeze_pol)


def run_trace(scheme_flags, cfg: SimConfig, trace: Trace, net,
              comp_ratio, warm_frac: float = 0.3,
              active_cu: int = None, policy=None,
              telemetry_cfg: telemetry.TelemetryConfig = None
              ) -> SimState:
    """Replay one trace under one scheme/net and return the final
    SimState — the state-level sibling of `simulate_grid`, for callers
    that need the movement internals (residency tier, fabric channel
    banks, NIC banks, link model, adapted ratios, per-module/per-unit
    byte ledgers, engine buffers) rather than the metrics dict.
    `active_cu` defaults to the full `cfg.num_cu` envelope; `policy`
    (PolicySpec / PolicyFlags / name) to the `SimConfig.fifo` alias;
    `telemetry_cfg` (STATIC) turns on the telemetry plane — the final
    state's `.tel` then carries the latency histogram and the sampled
    series ring (`SERIES_CHANNELS`) for `repro.runtime.obs` to export."""
    r = len(trace.page)
    ratio0 = as_traceable(scheme_flags).bw_ratio
    st = _init_state(cfg, trace.n_pages, net, ratio0, telemetry_cfg)
    step = make_step(scheme_flags, cfg, net, comp_ratio, warm_frac * r,
                     cfg.num_cu if active_cu is None else active_cu,
                     policy, telemetry_cfg)
    xs = (jnp.asarray(trace.page), jnp.asarray(trace.off),
          jnp.asarray(trace.gap), jnp.asarray(trace.wr))
    final, _ = jax.lax.scan(step, st, xs)
    return final


def simulate_grid(scheme_flags, cfg: SimConfig, trace: Trace,
                  nets, comp_ratio, warm_frac: float = 0.3):
    """One scheme x one trace over a list of network configs (a lattice of
    scheme-size 1 — kept for paired baseline/variant comparisons)."""
    return simulate_lattice([scheme_flags], cfg, trace, nets, comp_ratio,
                            warm_frac)[0]


def make_net(p: NetworkParams, num_mc: int = 1, bw_factors=None,
             switches=None, schedule=None) -> dict:
    """Network point: per-module base bandwidths + latencies + the link's
    time-varying schedule.

    `schedule` is a (sched_t (K,), mult (K,) or (K, M), health (K,) or
    (K, M)) triple — typically `repro.sim.workloads.make_link_schedule`
    output. Default: a K=1 constant, fully-healthy schedule, which is
    bit-identical to the pre-LinkModel scalar-bandwidth path (pinned by
    the seed golden). Within one `simulate_lattice` call every net must
    share a knot count so profiles stack on the net axis."""
    bw_factors = bw_factors or [p.bw_factor] * num_mc
    switches = switches or [p.switch_latency_ns] * num_mc
    if schedule is None:
        sched_t = np.zeros((1,), np.float32)
        mult = np.ones((1, num_mc), np.float32)
        health = np.ones((1, num_mc), np.float32)
    else:
        sched_t, mult, health = schedule
        sched_t = np.asarray(sched_t, np.float32)
        to_km = lambda a: np.broadcast_to(
            np.asarray(a, np.float32).reshape((len(sched_t), -1)),
            (len(sched_t), num_mc)).copy()
        mult, health = to_km(mult), to_km(health)
    return {
        "bw": np.asarray([p.dram_bw_gbps / f for f in bw_factors],
                         np.float32),
        "switch": np.asarray(switches, np.float32),
        "membw": np.float32(p.dram_bw_gbps),
        "local_lat": np.float32(p.local_mem_latency_ns),
        "remote_lat": np.float32(p.remote_mem_latency_ns),
        "trans_lat": np.float32(p.translation_latency_ns),
        "sched_t": sched_t,
        "sched_mult": mult,
        "sched_health": health,
    }
