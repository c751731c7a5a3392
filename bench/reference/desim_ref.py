"""Plain reference of the paper's request-level simulator, one request at a time.

For one scheme on one network it replays an LLC-miss trace through the
system the configuration states: a core with an MLP window of
outstanding misses, a set-associative local memory (LRU), the DaeMon
engine's inflight page and sub-block buffers with the selection rule
(section 4.2), and one memory module reached over a remote-memory bus and
a network link, each a line channel and a page channel (split by the
bandwidth ratio when the scheme partitions, one shared FIFO when it does
not), plus a writeback channel on the network. Simulated times are
float32, in the order the paper's model states them; `rnd` swaps in
another precision (the control). It imports nothing of the program under
test.

`simulate` returns the metrics one lattice cell reports.
"""
from __future__ import annotations

import heapq

import numpy as np

F32 = np.float32
BIG = F32(3.0e38)
NEVER = F32(3.4e38)
RATIO_MIN, RATIO_MAX, GAIN, EMA = 0.05, 0.75, 0.25, 0.08


def _f32(x):
    return F32(x)


class _Fabric:
    """One module's channel clocks plus the adaptive-ratio state."""

    def __init__(self, ratio, rnd):
        z = rnd(0.0)
        self.line_busy = self.page_busy = self.wb_busy = z
        self.ratio = rnd(ratio)
        self.line_rate = self.page_rate = z


def _adapt(fab, now, bw, page_unit, line_occ, page_occ, r_idle, rnd):
    """The section 4.1 controller step on one module's carried ratio."""
    z = rnd(0.0)
    line_bl = max(rnd(fab.line_busy - now), z)
    page_bl = max(rnd(fab.page_busy - now), z)
    tau = rnd(page_unit / max(bw, rnd(1e-6)))
    occ_t = rnd(rnd(line_occ + page_occ) * tau)
    load_t = rnd(rnd(line_bl + page_bl) + occ_t)
    sat = rnd(load_t / rnd(load_t + tau))
    total = rnd(fab.line_rate + fab.page_rate)
    if total > rnd(1e-6):
        prop = rnd(fab.line_rate / max(total, rnd(1e-6)))
    else:
        prop = r_idle
    sat = min(max(sat, z), rnd(1.0))
    target = rnd(rnd(sat * prop) + rnd(rnd(rnd(1.0) - sat) * r_idle))
    new = rnd(fab.ratio + rnd(rnd(GAIN) * rnd(target - fab.ratio)))
    fab.ratio = min(max(new, rnd(RATIO_MIN)), rnd(RATIO_MAX))


def _serve_dual(fab, partition, bw, line_ready, line_bytes, line_gate,
                page_ready, page_bytes, page_gate, rnd):
    """Line then page on one link; returns (line_done, page_done)."""
    if partition:
        line_bw = rnd(bw * fab.ratio)
        page_bw = rnd(bw * rnd(rnd(1.0) - fab.ratio))
        line_in = fab.line_busy
    else:
        line_bw = page_bw = rnd(bw * rnd(1.0))
        line_in = fab.page_busy
    line_done = rnd(max(line_ready, line_in)
                    + rnd(line_bytes / max(line_bw, rnd(1e-6))))
    lb = line_done if line_gate else line_in
    page_in = fab.page_busy if partition else lb
    page_done = rnd(max(page_ready, page_in)
                    + rnd(page_bytes / max(page_bw, rnd(1e-6))))
    if partition:
        fab.line_busy = lb
    fab.page_busy = page_done if page_gate else page_in
    a = rnd(EMA)
    keep = rnd(1 - EMA)
    fab.line_rate = rnd(rnd(keep * fab.line_rate)
                        + rnd(a * (line_bytes if line_gate else rnd(0.0))))
    fab.page_rate = rnd(rnd(keep * fab.page_rate)
                        + rnd(a * (page_bytes if page_gate else rnd(0.0))))
    return line_done, page_done


def simulate(cfg: dict, scheme: dict, net: tuple, trace, comp_ratio,
             rnd=_f32) -> dict:
    """One lattice cell. `net` is (switch_latency_ns, bw_factor); `trace`
    has arrays page, off, gap, wr and the footprint n_pages."""
    sim, dp, nw = cfg["sim"], cfg["daemon"], cfg["network"]
    page, off, gap, wr = (np.asarray(trace.page), np.asarray(trace.off),
                          np.asarray(trace.gap), np.asarray(trace.wr))
    n_req = len(page)
    ways = sim["ways"]
    sets = max(1, max(ways, int(trace.n_pages * sim["local_frac"])) // ways)
    lpp = dp["page_bytes"] // dp["line_bytes"]
    P, SB = dp["inflight_page_buf"], dp["inflight_sb_buf"]
    # flags
    local_only, move_lines = scheme["local_only"], scheme["move_lines"]
    move_pages, page_free = scheme["move_pages"], scheme["page_free"]
    partition, selection = scheme["partition"], scheme["selection"]
    compress, use_local = scheme["compress"], scheme["use_local_mem"]
    adaptive, r_idle = scheme["adaptive"], rnd(scheme["bw_ratio"])
    want_page = (move_pages or page_free) and use_local
    line_only = (not move_pages) and (not page_free)
    # network point
    sw = rnd(net[0])
    bw = rnd(nw["dram_bw_gbps"] / net[1])
    membw = rnd(nw["dram_bw_gbps"])
    local_lat = rnd(nw["local_mem_latency_ns"])
    remote_lat = rnd(nw["remote_mem_latency_ns"])
    trans_lat = rnd(nw["translation_latency_ns"])
    line_b, page_b = rnd(dp["line_bytes"]), rnd(dp["page_bytes"])
    comp_lat = rnd(dp["compress_cycles"] / dp["cpu_ghz"])
    wire_b = rnd(page_b / rnd(comp_ratio)) if compress else page_b
    comp_delay = comp_lat if compress else rnd(0.0)
    warm_after = rnd(sim["warm_frac"] * n_req)
    bw_f = max(bw, rnd(1e-6))
    sw2 = rnd(rnd(2.0) * sw)
    # state
    t = rnd(0.0)
    ring = [rnd(0.0)] * sim["mlp"]
    tpage = [[-1] * ways for _ in range(sets)]
    tage = [[rnd(0.0)] * ways for _ in range(sets)]
    tready = [[BIG] * ways for _ in range(sets)]
    tdirty = [[False] * ways for _ in range(sets)]
    pages_in = {}          # page -> [issue, arrival]: the inflight page CAM
    page_heap = []         # (arrival, page) of scheduled pages
    sb_live = {}           # sub-block entry id -> page
    sb_by_page = {}        # page -> set of entry ids
    sb_heap = []           # (arrival, id)
    sb_next = 0
    net_fab = _Fabric(scheme["bw_ratio"], rnd)
    mem_fab = _Fabric(scheme["bw_ratio"], rnd)
    st = dict(n=0, hits=0, lat_sum=rnd(0.0), pages_moved=0, lines_moved=0,
              net_bytes=rnd(0.0), page_drops=0)
    for i in range(n_req):
        pg, o, g, w = int(page[i]), int(off[i]), gap[i], bool(wr[i])
        oldest = min(ring)
        slot = ring.index(oldest)
        t_issue = max(rnd(t + rnd(g)), oldest)
        s = pg % sets
        row = tpage[s]
        present = pg in row
        way = row.index(pg) if present else 0
        ready_ok = tready[s][way] <= t_issue
        is_hit = (present and ready_ok and use_local) or local_only
        inflight_tbl = present and not ready_ok
        # retire arrivals by t_issue (pages, and lines of arrived pages)
        while page_heap and page_heap[0][0] <= t_issue:
            arr, p = heapq.heappop(page_heap)
            if p in pages_in and pages_in[p][1] == arr:
                del pages_in[p]
                for e in sb_by_page.pop(p, ()):
                    del sb_live[e]
        while sb_heap and sb_heap[0][0] <= t_issue:
            _, e = heapq.heappop(sb_heap)
            p = sb_live.pop(e, None)
            if p is not None:
                sb_by_page[p].discard(e)
        # selection (section 4.2)
        n_pg, n_sb = len(pages_in), len(sb_live)
        page_found = pg in pages_in
        page_util = rnd(n_pg / P)
        sb_util = rnd(n_sb / SB)
        send_page = (not page_found) and n_pg < P
        issued = page_found and pages_in[pg][0] <= t_issue
        if selection:
            send_line = (sb_util < rnd(page_util + rnd(0.0))
                         and not issued) if page_found else True
        else:
            send_line = True
        send_line = send_line and n_sb < SB
        pending = pages_in[pg][1] if page_found else BIG
        send_page = (send_page and want_page and not is_hit
                     and not inflight_tbl and not local_only)
        send_line = send_line and move_lines and not is_hit
        if line_only:
            send_line = not is_hit
        send_line = send_line and not local_only
        t0 = rnd(rnd(rnd(t_issue + sw) + trans_lat) + remote_lat)
        if adaptive:
            _adapt(net_fab, t_issue, bw, wire_b, sb_util, page_util, r_idle,
                   rnd)
            _adapt(mem_fab, t_issue, membw, page_b, sb_util, page_util,
                   r_idle, rnd)
        ratio = net_fab.ratio
        line_share = ratio if partition else rnd(1.0)
        page_share = rnd(rnd(1.0) - ratio) if partition else rnd(1.0)
        mem_line_share = mem_fab.ratio if partition else rnd(1.0)
        mpp = send_page and not page_free
        lm_done, pm_done = _serve_dual(mem_fab, partition, membw, t0, line_b,
                                       send_line, t0, page_b, mpp, rnd)
        ln_done, pn_done = _serve_dual(
            net_fab, partition, bw, lm_done, line_b, send_line,
            rnd(pm_done + comp_delay), wire_b, mpp, rnd)
        line_arrival = rnd(ln_done + sw) if send_line else BIG
        pn_start = rnd(pn_done - rnd(wire_b / max(rnd(bw_f * page_share),
                                                  rnd(1e-6))))
        page_arrival = (rnd(rnd(pn_done + sw) + comp_delay) if mpp
                        else BIG)
        if page_free and send_page:
            page_arrival = rnd(rnd(rnd(rnd(rnd(t_issue + sw2) + trans_lat)
                                       + remote_lat) + rnd(line_b / bw_f))
                               + rnd(line_b / membw))
        cand = min(min(line_arrival, page_arrival), pending)
        if cand >= rnd(BIG / 2):
            cand = rnd(rnd(rnd(rnd(rnd(t_issue + sw2) + trans_lat)
                               + remote_lat)
                           + rnd(line_b / rnd(bw_f * line_share)))
                       + rnd(line_b / rnd(membw * mem_line_share)))
        done = rnd(t_issue + local_lat) if is_hit else cand
        if send_page and n_pg < P:
            pages_in[pg] = [pn_start, page_arrival]
            heapq.heappush(page_heap, (page_arrival, pg))
        if send_line and move_lines and n_sb < SB:
            sb_live[sb_next] = pg
            sb_by_page.setdefault(pg, set()).add(sb_next)
            heapq.heappush(sb_heap, (line_arrival, sb_next))
            sb_next += 1
        # local memory: insert at the LRU victim, writeback a dirty one
        do_insert = send_page and use_local
        ages = tage[s]
        victim = ages.index(min(ages))
        evict_page = tpage[s][victim]
        wb = do_insert and tdirty[s][victim] and evict_page >= 0
        wb_bytes = wire_b if wb else rnd(0.0)
        if wb:
            net_fab.wb_busy = rnd(max(t_issue, net_fab.wb_busy)
                                  + rnd(wire_b / bw))
        if do_insert:
            tpage[s][victim] = pg
            tage[s][victim] = t_issue
            tready[s][victim] = page_arrival
            tdirty[s][victim] = w
        if is_hit and present:
            tage[s][way] = max(tage[s][way], t_issue)
            if w:
                tdirty[s][way] = True
        warm = rnd(i) >= warm_after
        lat = rnd(done - t_issue) if warm else rnd(0.0)
        st["n"] += warm
        st["hits"] += (is_hit or inflight_tbl) and warm
        st["lat_sum"] = rnd(st["lat_sum"] + lat)
        st["pages_moved"] += mpp
        st["lines_moved"] += send_line
        nb = rnd(st["net_bytes"] + wb_bytes)
        nb = rnd(nb + (wire_b if mpp else rnd(0.0)))
        st["net_bytes"] = rnd(nb + (line_b if send_line else rnd(0.0)))
        st["page_drops"] += ((not is_hit) and (not send_page)
                             and (not page_found) and (not inflight_tbl)
                             and want_page)
        t = t_issue
        ring[slot] = done
    total = max(max(ring), t)
    n = rnd(st["n"])
    misses = max(rnd(n - rnd(st["hits"])), rnd(1.0))
    return {
        "total_time_ns": float(total),
        "avg_miss_ns": float(rnd(st["lat_sum"] / misses)),
        "avg_access_ns": float(rnd(st["lat_sum"] / max(n, rnd(1.0)))),
        "hit_ratio": float(rnd(rnd(st["hits"]) / max(n, rnd(1.0)))),
        "pages_moved": float(st["pages_moved"]),
        "lines_moved": float(st["lines_moved"]),
        "net_bytes": float(st["net_bytes"]),
        "page_drops": float(st["page_drops"]),
    }
