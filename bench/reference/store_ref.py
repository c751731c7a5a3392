"""Plain reference of the two-tier paged KV store, one request at a time.

It replays the same request streams as the store under test and states,
in straight Python, what each decode step must do: land the pages whose
transfer has completed into the policy's victim slots (LRU inside each
set of the local pool), probe and serve the requests, then route every
miss, in tenant order and request order, through the selection rule and
the shared per-module channels (a line channel and a page channel split
by the bandwidth ratio, plus a writeback channel). Simulated times are
float32, computed in the same order as the store states them, so that
the two agree to rounding; `rnd` swaps in another precision (the
control). It imports nothing of the program under test.

Returns, per run: the per-step hit flags, the final page tables, the
integer ledger and the per-module wire bytes.
"""
from __future__ import annotations

import numpy as np

F32 = np.float32
BIG = F32(3.0e38)         # residency "never ready"
NEVER = F32(3.4e38)       # engine "never arrives"
SCHEDULED, THROTTLED = 1, 3


class StoreSpec:
    """The store's parameters, as the configuration states them."""

    def __init__(self, cfg: dict, batch: int):
        st = cfg["store"]
        self.B = batch
        self.N = st["local_pages_per_tenant"]
        self.W = st["pool_ways"]
        self.S = self.N // self.W
        dp = cfg["daemon"]
        self.P = dp["inflight_page_buf"]
        self.SB = dp["inflight_sb_buf"]
        self.M = st["memory_modules"]
        self.page_tokens = st["page_tokens"]
        self.budget = st["page_budget_per_step"]
        self.ratio = dp["bw_ratio"]
        self.lpp = dp["page_bytes"] // dp["line_bytes"]
        self.dirty_threshold = dp["dirty_flush_threshold"]
        token_bytes = float(cfg["model"]["num_key_value_heads"]
                            * cfg["model"]["head_dim"] * 2 * 2)
        raw_page = self.page_tokens * token_bytes
        self.line_wire = token_bytes                  # one token, raw
        self.page_raw = raw_page
        # int8 link compression: int8 payload + one f32 scale per 256
        self.page_wire = (raw_page / 2 + raw_page / 2 / 256 * 4
                          if st["compress_pages"] else raw_page)
        self.bw = self.budget * token_bytes / (1.0 - self.ratio)
        self.nominal = float(max(1, round(self.page_tokens / self.budget)))


class _Tenant:
    def __init__(self, sp: StoreSpec):
        self.page = [[-1] * sp.W for _ in range(sp.S)]
        self.age = [[F32(0.0)] * sp.W for _ in range(sp.S)]
        self.ready = [[BIG] * sp.W for _ in range(sp.S)]
        self.dirty = [[False] * sp.W for _ in range(sp.S)]
        self.pkey = [-1] * sp.P
        self.pstate = [0] * sp.P
        self.parr = [NEVER] * sp.P
        self.pissue = [NEVER] * sp.P
        self.pdirty = [0] * sp.P
        self.sbkey = [-1] * sp.SB
        self.sbarr = [NEVER] * sp.SB
        self.stats = dict.fromkeys(LEDGER_KEYS, 0)


LEDGER_KEYS = ("requests", "local_hits", "page_moves", "sub_block_fetches",
               "evictions", "dirty_evicts", "wire_bytes",
               "writeback_bytes", "uncompressed_bytes")


def _f32(x):
    return F32(x)


def run(sp: StoreSpec, pages, offs, writes, rnd=_f32) -> dict:
    """Replay (T, B, R) streams from a fresh store. `rnd` rounds every
    simulated time to the working precision (float32 by default)."""
    pages = np.asarray(pages)
    offs = np.asarray(offs)
    writes = np.asarray(writes)
    T, B, R = pages.shape
    assert B == sp.B
    ten = [_Tenant(sp) for _ in range(B)]
    M = sp.M
    line_busy = [rnd(0.0)] * M
    page_busy = [rnd(0.0)] * M
    wb_busy = [rnd(0.0)] * M
    mod_bytes = [0.0] * M
    bw = rnd(sp.bw)
    line_bw = rnd(bw * rnd(sp.ratio))
    page_share = rnd(rnd(1.0) - rnd(sp.ratio))
    page_bw = rnd(bw * page_share)
    line_wire, page_wire = rnd(sp.line_wire), rnd(sp.page_wire)
    line_dt = rnd(line_wire / line_bw)
    page_dt = rnd(page_wire / page_bw)
    wb_dt = rnd(page_wire / bw)
    nominal = rnd(sp.nominal)
    hits_all = np.zeros((T, B, R), bool)
    clock = rnd(0.0)
    for t in range(T):
        clock = rnd(clock + rnd(1.0))
        evicted_all = []
        # ---- residency transaction, tenant by tenant ----
        for b, tn in enumerate(ten):
            landed = [i for i in range(sp.P)
                      if tn.pstate[i] == SCHEDULED and tn.parr[i] <= clock]
            evicted = [-1] * min(sp.P, sp.N)
            if landed:
                # victims from the pre-landing table, per set in LRU order
                order = {}
                rank_in_set = {}
                for j, i in enumerate(landed):
                    pid = tn.pkey[i]
                    s = pid % sp.S
                    if s not in order:
                        order[s] = sorted(range(sp.W),
                                          key=lambda w, s=s: tn.age[s][w])
                    rank = rank_in_set.get(s, 0)
                    rank_in_set[s] = rank + 1
                    if rank >= sp.W:
                        continue                  # same-set overflow drops
                    w = order[s][rank]
                    victim = tn.page[s][w]
                    if victim >= 0:
                        tn.stats["evictions"] += 1
                        if tn.dirty[s][w]:
                            evicted[j] = victim
                    tn.page[s][w] = pid
                    tn.age[s][w] = clock
                    tn.ready[s][w] = clock
                    tn.dirty[s][w] = False
            evicted_all.append(evicted)
            for r in range(R):
                pid = int(pages[t, b, r])
                s = pid % sp.S
                row = tn.page[s]
                if pid in row:
                    w = row.index(pid)
                    if tn.ready[s][w] <= clock:
                        hits_all[t, b, r] = True
                        tn.age[s][w] = max(tn.age[s][w], clock)
                        if writes[t, b, r]:
                            tn.dirty[s][w] = True
            # retire arrivals: landed pages and their pending lines
            arrived = {tn.pkey[i] for i in landed}
            for i in landed:
                tn.pkey[i], tn.pstate[i] = -1, 0
                tn.parr[i] = tn.pissue[i] = NEVER
                tn.pdirty[i] = 0
            for i in range(sp.SB):
                k = tn.sbkey[i]
                if k >= 0 and (tn.sbarr[i] <= clock
                               or k // sp.lpp in arrived):
                    tn.sbkey[i], tn.sbarr[i] = -1, NEVER
        # ---- scheduling fold over tenants, on the shared channels ----
        for b, tn in enumerate(ten):
            n_wb = 0
            for pid in evicted_all[b]:
                if pid < 0:
                    continue
                mc = pid % M
                found = pid in tn.pkey
                idx = tn.pkey.index(pid) if found else 0
                cnt = tn.pdirty[idx] + 1 if found else 0
                over = cnt > sp.dirty_threshold
                if found and over:
                    tn.pstate[idx] = THROTTLED
                tn.pdirty[idx] = cnt if (found and not over) else 0
                if found and not over:
                    continue                      # buffered in the engine
                wb_busy[mc] = rnd(max(clock, wb_busy[mc]) + wb_dt)
                mod_bytes[mc] += sp.page_wire
                n_wb += 1
            n_sub = n_sched = 0
            for r in range(R):
                pid = int(pages[t, b, r])
                off = int(offs[t, b, r]) % sp.lpp
                mc = pid % M
                backlog = rnd(max(rnd(page_busy[mc] - clock), rnd(0.0)))
                pressure = rnd(backlog / rnd(backlog + nominal))
                found = pid in tn.pkey
                pidx = tn.pkey.index(pid) if found else -1
                n_pages = sum(k >= 0 for k in tn.pkey)
                n_lines = sum(k >= 0 for k in tn.sbkey)
                page_room = n_pages < sp.P
                sb_room = n_lines < sp.SB
                page_util = rnd(n_pages / sp.P)
                sb_util = rnd(n_lines / sp.SB)
                send_page = (not found) and page_room
                issued = found and tn.pissue[pidx] <= clock
                if found:
                    send_line = (sb_util < rnd(page_util + pressure)
                                 and not issued)
                else:
                    send_line = True
                send_line = send_line and sb_room
                miss = not hits_all[t, b, r]
                do_page = miss and send_page
                do_line = miss and send_line
                line_done = rnd(max(clock, line_busy[mc]) + line_dt)
                page_done = rnd(max(clock, page_busy[mc]) + page_dt)
                if do_line:
                    line_busy[mc] = line_done
                    mod_bytes[mc] += sp.line_wire
                    n_sub += 1
                if do_page:
                    page_busy[mc] = page_done
                    mod_bytes[mc] += sp.page_wire
                    n_sched += 1
                    i = tn.pkey.index(-1)
                    tn.pkey[i], tn.pstate[i] = pid, SCHEDULED
                    tn.parr[i] = page_done
                    tn.pissue[i] = rnd(page_done - page_dt)
                    tn.pdirty[i] = 0
                if do_line:
                    i = tn.sbkey.index(-1)
                    tn.sbkey[i] = pid * sp.lpp + off
                    tn.sbarr[i] = line_done
            st = tn.stats
            st["requests"] += R
            st["local_hits"] += int(hits_all[t, b].sum())
            st["page_moves"] += n_sched
            st["sub_block_fetches"] += n_sub
            st["dirty_evicts"] += n_wb
            st["wire_bytes"] += (n_sub * sp.line_wire
                                 + (n_sched + n_wb) * sp.page_wire)
            st["writeback_bytes"] += n_wb * sp.page_wire
            st["uncompressed_bytes"] += (n_sub * sp.line_wire
                                         + (n_sched + n_wb) * sp.page_raw)
    ledger = {k: float(sum(tn.stats[k] for tn in ten)) for k in LEDGER_KEYS}
    return {"hits": hits_all,
            "tables": np.array([tn.page for tn in ten], np.int64),
            "ledger": ledger, "module_bytes": mod_bytes}
