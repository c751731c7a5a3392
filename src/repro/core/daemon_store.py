"""DaemonKVStore: two-tier paged KV cache with DaeMon movement policies.

The serving-side integration of the paper: a small *local* (HBM) page pool
holds hot KV pages per sequence; the full KV lives in the *remote* tier
(host memory or remote pods — here a jnp array standing in for the remote
pool, with transfers accounted by the movement planner). Per decode step
the engine:

  1. looks the needed pages up in the local page table — the shared
     *residency plane* (``repro.core.residency``): the same tier
     state/primitives and the same replacement-policy registry (LRU /
     FIFO / RRIP / dirty-averse, ``KVStoreConfig.policy``) the
     simulator's per-unit tables run on, here as one fully-associative
     set (ways = pool slots),
  2. serves misses through the *sub-block plane* (single-token critical
     fetch, `kernels.paged_gather`) immediately,
  3. schedules *page plane* migrations through the shared movement fabric
     (`repro.core.fabric`): per-module bw_ratio-partitioned virtual
     channels over a possibly *time-varying* `LinkModel` (per-module
     bandwidth schedules + health masks, sampled at the decode-step
     clock), int8-compressed payloads — §4.1/§4.4,
  4. adapts granularity to the inflight-buffer occupancies AND the target
     module's channel backlog (§4.2 + fabric pressure), and — when
     `adaptive_ratio` is set — adapts the §4.1 partition ratio itself
     (the fabric's carried per-module ratio, `bandwidth.adapt_ratio`).

Neither the inflight-buffer machinery nor the channel arithmetic is
reimplemented here: the store embeds a ``repro.core.engine.EngineState``
per sequence and a ``repro.core.fabric.FabricState`` shared by the whole
batch, and routes every decision through ``select_granularity`` /
``schedule_page`` / ``schedule_line`` / ``poll_arrivals`` /
``retire_arrivals`` and every transfer through ``fabric.serve_dual_at``
(itself a thin per-module wrapper over ``bandwidth.serve_dual``) — the
same primitives the simulator's per-request transition uses, so the
serving path and the simulator cannot diverge on module routing, channel
arithmetic, or buffer semantics by construction. The clock is the
decode-step counter; page arrival times are real channel-service
completions, so congestion on a module's page channel delays landings
exactly as in the simulator. One deliberate serving-side extension: the
store feeds ``fabric.backlog`` into ``select_granularity`` as
``module_pressure`` (the simulator keeps the paper's pressure-free §4.2
rule, pinned by the seed golden capture).

Multi-tenant batching: ``step_fetch_batch`` carries B independent
sequences (own pool, page table, engine, ledger — a leading batch axis on
``SeqState``) against ONE fabric: landing/lookup/serve are ``vmap``ped
across the batch, then scheduling folds over the batch in sequence order
so all B engines contend for the same per-module channels
deterministically. ``step_fetch`` is the single-sequence wrapper.

Page layouts: a page is a K and a V tensor of (page_tokens, kv_heads,
head_dim) each, or — for multi-head latent attention (DeepSeek-V2/V3's
MLA) — ONE latent tensor of `latent_dim` values a token (the compressed
c_KV and the shared RoPE key, which serve as key and value alike;
`KVStoreConfig.latent_dim`). A latent store holds one pool, reads one
tier tensor (passed as `remote_k`, with `remote_v=None`), serves k with
v None, and prices its pages at `latent_dim` bfloat16 values a token.

Replicated serving (the compute plane, ``repro.core.compute_plane``):
``step_fetch_replicated`` carries C serving replicas x B tenants each —
C*B sequences — against ONE memory-side fabric plus a per-replica NIC
channel bank: every transfer is priced on two legs (the shared module's
channel AND the owning replica's NIC, arrival = the later completion),
so replicas contend on the shared pool while their own ingress
serializes independently. Per-unit wire bytes accrue on the NIC bank's
ledgers (``ledger()`` reports them as ``unit_bytes``). A C=1 replica
set keeps the NIC leg gated off and is exactly ``step_fetch_batch``.

Writeback path (§4.3 serving side): locally *written* KV pages (marked
via the steppers' ``needed_writes``) that get evicted from the local
pool are routed through ``engine.note_dirty_eviction`` (dirty-unit
buffering + throttle) and, when not buffered, serialized on the target
module's writeback channel (``fabric.serve_writeback_at``) — the same
wire accounting desim applies to its dirty evictions.
``stats['writeback_bytes']`` tracks the wire cost; it is included in
``wire_bytes`` so the byte-conservation invariant (fabric ledgers ==
stats) keeps holding. One deliberate semantic difference from desim: a
write whose page is NOT resident is a write-through — there is no local
copy to dirty, the append lands in the remote tier directly, and the
page fetched later is a clean remote copy (desim instead inserts its
table entry at miss time and carries the triggering request's write
flag into it). Only write HITS dirty the resident copy.

Hot-path implementation (the kernel plane, DESIGN.md §9): steps 1 + 3's
per-sequence residency transaction — landing compaction, victim
selection, dirty-eviction enqueue, pool scatter, CAM probe, hit gather,
policy touch — is served by ONE fused op (``ops.residency_fused`` via
``_transact``), selected by the STATIC ``KVStoreConfig.kernel_impl``
lattice: ``"auto"`` (Pallas kernel on TPU, its jnp oracle elsewhere),
``"pallas"``, ``"ref"`` (the oracle, ``kernels.ref``), or ``"chain"``
(the legacy per-primitive ``_land``/``_lookup`` path, kept as the
bit-identical benchmark comparator). ``pool_ways`` generalizes the pool
to a sets x ways geometry (0 = fully-associative, the default).

All state is a pytree; both steppers are jit/scan-friendly. The byte
ledger (`stats` + the fabric's per-module byte counters) is what
examples/serve_paged.py reports against the Remote (page-only) baseline.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import (bandwidth, compute_plane, fabric, residency,
                        telemetry)
from repro.core.engine import (EngineState, find, gate_tree as _gate_tree,
                               init_engine_state, note_dirty_eviction,
                               poll_arrivals, retire_arrivals,
                               schedule_line, schedule_page,
                               select_granularity, utilization)
from repro.core.fabric import FabricConfig, FabricState, LinkModel
from repro.core.params import DaemonParams
from repro.kernels import ops
from repro.kernels.residency_fused import LANES

F32 = jnp.float32
BIG = jnp.float32(3.0e38)
LATENT_TILE = 16     # sublanes of a bfloat16 (16, 128) vector tile


@dataclass(frozen=True)
class KVStoreConfig:
    num_local_pages: int          # HBM pool slots (per sequence)
    page_tokens: int              # tokens per page
    kv_heads: int = 0             # K/V layout: heads a token (and below)
    head_dim: int = 0
    daemon: DaemonParams = DaemonParams()
    compress_pages: bool = True   # int8 link compression on page moves
    page_budget_per_step: int = 4  # page-plane raw tokens drained per step
    selection: bool = True        # §4.2 adaptive granularity (else both)
    adaptive_ratio: bool = False  # §4.1 ratio as adapted fabric state
    fabric: FabricConfig = FabricConfig()  # modules + placement
    policy: str = "lru"           # pool replacement (residency.POLICIES)
    pool_ways: int = 0            # set-assoc pool geometry; 0 = fully assoc
    kernel_impl: str = "auto"     # hot-path impl: auto|pallas|ref|chain
    # telemetry plane (DESIGN.md §10): STATIC level axis like
    # `kernel_impl`. "off" (default) is bit-identical to the
    # pre-telemetry store — `SeqState.tel` stays None, zero extra leaves
    # or ops in the compiled steppers. Histogram unit: decode STEPS
    # (per-request stall), so lat_lo/lat_hi default to a step range.
    telemetry: telemetry.TelemetryConfig = telemetry.TelemetryConfig(
        lat_lo=0.01, lat_hi=1e4)
    # page layout: 0 (default) = a K and a V tensor of (page_tokens,
    # kv_heads, head_dim) a page; > 0 = ONE latent tensor of `latent_dim`
    # values a token (MLA's compressed c_KV plus the shared RoPE key,
    # which serves as key and value alike; kv_heads/head_dim stay 0),
    # held token-major as whole (16, 128) bfloat16 tiles (`page_row`)
    latent_dim: int = 0

    def __post_init__(self):
        if self.policy not in residency.POLICIES:
            raise ValueError(f"policy must be one of "
                             f"{tuple(residency.POLICIES)}, "
                             f"got {self.policy!r}")
        if self.kernel_impl not in KERNEL_IMPLS:
            raise ValueError(f"kernel_impl must be one of {KERNEL_IMPLS},"
                             f" got {self.kernel_impl!r}")
        if self.pool_ways > 0 and self.num_local_pages % self.pool_ways:
            raise ValueError(f"pool_ways={self.pool_ways} must divide "
                             f"num_local_pages={self.num_local_pages}")
        if self.latent_dim > 0:
            if self.kv_heads or self.head_dim:
                raise ValueError("a latent store has no K/V heads: leave "
                                 "kv_heads and head_dim at 0")
            if self.page_tokens * self.latent_dim % (LATENT_TILE * LANES):
                raise ValueError(
                    f"a latent page of {self.page_tokens} x "
                    f"{self.latent_dim} values is not whole "
                    f"({LATENT_TILE}, {LANES}) tiles")
        elif self.kv_heads <= 0 or self.head_dim <= 0:
            raise ValueError("a K/V store needs kv_heads and head_dim "
                             "(or a latent_dim)")

    def policy_flags(self) -> residency.PolicyFlags:
        return residency.as_policy(self.policy)

    def page_row(self) -> Tuple[int, ...]:
        """Shape of one page of one tensor, as the pool, the remote tier
        and the served rows hold it: (page_tokens, kv_heads, head_dim)
        for K and for V, or (page_tokens * latent_dim / 128, 128) for a
        latent page — a reshape of (page_tokens, latent_dim), in whole
        tiles the kernels' DMAs move without a relayout."""
        if self.latent_dim > 0:
            return (self.page_tokens * self.latent_dim // LANES, LANES)
        return (self.page_tokens, self.kv_heads, self.head_dim)

    def pool_geometry(self) -> Tuple[int, int]:
        """(sets, ways) of the local page table. The default (pool_ways
        = 0) is the store's historical ONE fully-associative set; a
        positive `pool_ways` splits the same N slots into N/ways sets —
        the geometry the fused kernel's O(W^2) in-kernel victim ranking
        is sized for (production shapes run e.g. 256x16)."""
        if self.pool_ways <= 0:
            return 1, self.num_local_pages
        return self.num_local_pages // self.pool_ways, self.pool_ways


def _flat(tbl: jnp.ndarray) -> jnp.ndarray:
    """Collapse a fully-associative residency leaf's (1, N) table axes to
    the store's historical flat (N,) slot view (batch axes preserved)."""
    return tbl.reshape(tbl.shape[:-2] + (-1,))


class SeqState(NamedTuple):
    """Per-sequence tier state. In a batched store every leaf carries a
    leading (B,) axis; the fabric is deliberately NOT in here — it is the
    shared resource the batch contends for."""
    # local pool: (N, *cfg.page_row()) a tensor — k and v, or the latent
    # page in `kpool` with `vpool` None (no leaves)
    kpool: jnp.ndarray
    vpool: jnp.ndarray
    # local page table: the shared residency tier (repro.core.residency),
    # (S, W) per cfg.pool_geometry() (default ONE fully-associative set,
    # leaves (1, N)); flat pool slot = set * W + way
    res: residency.ResidencyState
    # DaeMon movement plane (inflight page + sub-block CAMs, §4.2)
    eng: EngineState
    stats: dict
    # telemetry plane (per-TENANT: replicated with the sequence, so a
    # batched store carries one stall histogram + series ring per
    # tenant); None when `cfg.telemetry.level == "off"` — a leafless
    # pytree, the compiled steppers are unchanged
    tel: telemetry.TelemetryState = None

    # flat (N,) views of the tier metadata (the store's historical slot
    # layout — callers and ledger readers keep indexing by pool slot)
    @property
    def slot_page(self) -> jnp.ndarray:
        return _flat(self.res.page)

    @property
    def slot_age(self) -> jnp.ndarray:
        return _flat(self.res.age)

    @property
    def slot_dirty(self) -> jnp.ndarray:
        return _flat(self.res.dirty)


class KVStoreState(NamedTuple):
    seq: SeqState
    fab: FabricState              # per-module channel bank + byte ledgers
    clock: jnp.ndarray            # scalar step counter

    # convenience passthroughs (callers read movement state directly)
    @property
    def eng(self) -> EngineState:
        return self.seq.eng

    @property
    def stats(self) -> dict:
        return self.seq.stats

    @property
    def slot_page(self) -> jnp.ndarray:
        return self.seq.slot_page

    @property
    def slot_age(self) -> jnp.ndarray:
        return self.seq.slot_age

    @property
    def kpool(self) -> jnp.ndarray:
        return self.seq.kpool

    @property
    def vpool(self) -> jnp.ndarray:
        return self.seq.vpool


class BatchedKVStoreState(NamedTuple):
    seqs: SeqState                # leaves have a leading (B,) axis
    fab: FabricState              # ONE bank shared by the whole batch
    clock: jnp.ndarray

    @property
    def stats(self) -> dict:
        return self.seqs.stats


class ReplicatedKVStoreState(NamedTuple):
    """C serving replicas x B tenants each: sequence leaves carry a
    leading (C*B,) axis (replica-major — sequence i belongs to replica
    i // B); `fab` is the ONE memory-side bank every replica contends
    on; `nic` is the per-replica compute-side NIC bank (C units)."""
    seqs: SeqState                # leaves have a leading (C*B,) axis
    fab: FabricState              # shared memory-side bank (M modules)
    nic: FabricState              # per-replica NIC banks (C units)
    clock: jnp.ndarray

    @property
    def num_replicas(self) -> int:
        return self.nic.line_busy.shape[0]

    @property
    def batch(self) -> int:
        return self.seqs.slot_page.shape[0] // self.num_replicas

    @property
    def stats(self) -> dict:
        return self.seqs.stats


STAT_KEYS = ("sub_block_fetches", "page_moves", "wire_bytes",
             "uncompressed_bytes", "local_hits", "requests", "stall_steps",
             "writeback_bytes", "dirty_evicts", "evictions",
             "writeback_offers", "landings", "remote_rows",
             "request_iterations")

# per-decode-step series channels the telemetry ring samples (the
# post-schedule fabric/stats view of the sequence's step)
SERIES_CHANNELS = ("page_backlog_steps", "ratio", "hit_rate", "evictions",
                   "writeback_bytes", "health")

# hot-path implementations: "auto" = fused Pallas kernel on TPU, fused
# jnp oracle elsewhere; "pallas"/"ref" force one fused side; "chain" =
# the legacy per-primitive _land/_lookup op chain (kept as the
# benchmark comparator and the seed-pinned reference)
KERNEL_IMPLS = ("auto", "pallas", "ref", "chain")


def _init_seq(cfg: KVStoreConfig) -> SeqState:
    shape = (cfg.num_local_pages,) + cfg.page_row()
    return SeqState(
        kpool=jnp.zeros(shape, jnp.bfloat16),
        vpool=None if cfg.latent_dim else jnp.zeros(shape, jnp.bfloat16),
        res=residency.init_residency(*cfg.pool_geometry()),
        eng=init_engine_state(cfg.daemon),
        stats={k: jnp.zeros((), F32) for k in STAT_KEYS},
        tel=telemetry.init_state(cfg.telemetry, len(SERIES_CHANNELS)),
    )


def default_link(cfg: KVStoreConfig) -> LinkModel:
    """Constant, fully healthy per-module link at the store's nominal
    bandwidth (`link_bytes_per_step`) — the pre-LinkModel semantics."""
    return fabric.constant_link(link_bytes_per_step(cfg),
                                cfg.fabric.num_modules)


def _init_fab(cfg: KVStoreConfig, link: LinkModel = None) -> FabricState:
    return fabric.init_fabric(cfg.fabric,
                              link=default_link(cfg) if link is None
                              else link,
                              ratio=cfg.daemon.bw_ratio)


def init_kv_store(cfg: KVStoreConfig, link: LinkModel = None
                  ) -> KVStoreState:
    """`link` (optional) swaps the constant default for a time-varying
    per-module `LinkModel` whose schedule is sampled at the decode-step
    clock — the serving-side robustness axis (bursts, degradation, link
    flaps). Knot times are in decode steps."""
    return KVStoreState(seq=_init_seq(cfg), fab=_init_fab(cfg, link),
                        clock=jnp.zeros((), F32))


def init_kv_store_batch(cfg: KVStoreConfig, batch: int,
                        link: LinkModel = None) -> BatchedKVStoreState:
    seqs = compute_plane.replicate(_init_seq(cfg), batch)
    return BatchedKVStoreState(seqs=seqs, fab=_init_fab(cfg, link),
                               clock=jnp.zeros((), F32))


def init_kv_store_replicated(cfg: KVStoreConfig, num_replicas: int,
                             batch: int, link: LinkModel = None,
                             nic_link: LinkModel = None
                             ) -> ReplicatedKVStoreState:
    """C replicas x B tenants against one shared memory-side fabric.

    `link` is the (optionally time-varying) memory-side LinkModel as in
    `init_kv_store_batch`; `nic_link` overrides the per-replica NIC link,
    which otherwise derives from the memory link (its mean per-module
    bandwidth + ambient schedule, `compute_plane.nic_link_for`)."""
    seqs = compute_plane.replicate(_init_seq(cfg), num_replicas * batch)
    fab = _init_fab(cfg, link)
    if nic_link is None:
        nic_link = compute_plane.nic_link_for(fab.link, num_replicas)
    nic = compute_plane.init_nic_bank(num_replicas, link=nic_link,
                                      ratio=cfg.daemon.bw_ratio)
    return ReplicatedKVStoreState(seqs=seqs, fab=fab, nic=nic,
                                  clock=jnp.zeros((), F32))


def _token_bytes(cfg: KVStoreConfig) -> float:
    if cfg.latent_dim > 0:
        return float(cfg.latent_dim * 2)               # one latent, bf16
    return float(cfg.kv_heads * cfg.head_dim * 2 * 2)  # k+v bf16


def _wire_bytes(cfg: KVStoreConfig, tokens: int, compressed: bool) -> float:
    raw = tokens * _token_bytes(cfg)
    if not compressed:
        return float(raw)
    # int8 payload + one f32 scale per 256-block
    return float(raw / 2 + raw / 2 / 256 * 4)


def link_bytes_per_step(cfg: KVStoreConfig) -> float:
    """Per-module physical link bandwidth in bytes per decode step.

    Sized so the page channel's (1 - bw_ratio) share drains exactly
    `page_budget_per_step` raw tokens per step — the partitioned-budget
    semantics the store always had, now expressed as channel bandwidth
    instead of a fixed per-page cost."""
    r = cfg.daemon.bw_ratio
    return cfg.page_budget_per_step * _token_bytes(cfg) / (1.0 - r)


def page_cost_steps(cfg: KVStoreConfig) -> int:
    """Nominal (uncongested, UNcompressed) page service time in decode
    steps. No longer an arrival time — arrivals come from the fabric's
    real channel service, and a compressed page on an idle channel lands
    in roughly half this — just the natural normalizer for module
    pressure and the scale tests wait on before expecting landings."""
    return max(1, round(cfg.page_tokens / cfg.page_budget_per_step))


# ------------------------------------------------------------ landing
def _land(seq: SeqState, cfg: KVStoreConfig, remote_k, remote_v, clock,
          pol: residency.PolicyFlags) -> Tuple[SeqState, jnp.ndarray]:
    """Land arrived pages into the replacement policy's victim slots.

    Returns (seq', evicted) where `evicted` (k_land,) int32 holds the
    page ids of locally-written (dirty) pages this landing evicted from
    the pool (-1 elsewhere) — the caller routes them through the
    dirty-eviction writeback path on the shared fabric (the landing
    itself cannot: it is vmapped per sequence, the fabric is shared).

    Landed inflight entries are compacted to the front so the remote tier
    is gathered ONCE for at most min(P, N) actually-landed pages —
    previously every one of the P inflight slots paid a full K+V page
    gather every step, landed or not — and the whole landing body is
    skipped (`lax.cond`) on the common steady-state steps where nothing
    arrives (under the batched path's `vmap` the cond lowers to a select,
    so there it costs one bounded gather per step). The j-th landed entry
    (slot order) takes the rank-j victim of its own set
    (`residency.landing_victims` — with the default fully-associative
    geometry exactly the first k of `evict_order`, under LRU the
    lowest-age victims).

    More than N pages landing on one step (possible with a wide fabric
    and budgets >= page_tokens) lands the first N in slot order; the
    excess entries are retired un-landed — a dropped migration, like the
    simulator's `page_drops` (as are same-set overflow landings under a
    set-associative `pool_ways` geometry). The pool is a cache, so a
    later touch just re-requests them; their wire bytes were genuinely
    spent.

    This is the LEGACY per-primitive chain (`kernel_impl="chain"`); the
    default store serves the same transaction through the fused kernel
    path (`_transact` -> `ops.residency_fused`), bit-identical by
    construction and pinned by tests/test_residency_fused.py.
    """
    landed, landed_pages = poll_arrivals(seq.eng, clock)
    p = int(landed.shape[0])
    w_ways = seq.res.page.shape[-1]
    k_land = min(p, cfg.num_local_pages)
    no_evict = jnp.full((k_land,), -1, jnp.int32)

    def do_land(seq):
        order = jnp.argsort(jnp.logical_not(landed).astype(jnp.int32),
                            stable=True)
        pick = order[:k_land]
        do = landed[pick]
        pids = landed_pages[pick]
        pages = jax.tree.map(
            lambda tier, pool: ops.paged_gather(
                tier, jnp.maximum(pids, 0)).astype(pool.dtype),
            (remote_k, remote_v), (seq.kpool, seq.vpool))
        sets, vways, ok = residency.landing_victims(seq.res, pids, pol)
        do = do & ok
        victims = sets * w_ways + vways              # flat pool slots
        resident = seq.slot_page[victims] >= 0
        evicted = jnp.where(do & seq.slot_dirty[victims] & resident,
                            seq.slot_page[victims], no_evict)

        def put(tbl, val):
            # masked lanes scatter out of bounds and drop — a clamped
            # duplicate target must never clobber a live landing
            return tbl.at[jnp.where(do, victims, tbl.shape[0])].set(
                val, mode="drop")

        # a freshly landed page is a clean remote copy (dirty=False)
        res = residency.insert(seq.res, sets, vways,
                               pids, now=clock, ready=clock, dirty=False,
                               gate=do)
        stats = {**seq.stats,
                 "evictions": seq.stats["evictions"]
                 + jnp.sum(do & resident)}
        kpool, vpool = jax.tree.map(put, (seq.kpool, seq.vpool), pages)
        return seq._replace(res=res, stats=stats, kpool=kpool,
                            vpool=vpool), evicted

    seq, evicted = jax.lax.cond(jnp.any(landed), do_land,
                                lambda s: (s, no_evict), seq)
    return seq._replace(eng=retire_arrivals(seq.eng, clock,
                                            cfg.daemon.lines_per_page)
                        ), evicted


# ------------------------------------------------------------- lookup
def _lookup(seq: SeqState, clock, needed_pages, needed_writes,
            pol: residency.PolicyFlags):
    """Vectorized CAM lookup + local-pool serve — after landing, so a page
    that arrives this step hits immediately (the residency tier's `ready`
    in-flight tag, desim's tbl_valid <= t_issue). `needed_writes` marks
    requests that WRITE their page (KV append): a written resident page
    turns dirty — its eventual eviction owes a writeback (scatter-max:
    duplicate slots OR their write flags). The hit-time age refresh is
    policy-gated (`residency.touch`): LRU refreshes, FIFO keeps insert
    order.
    """
    present, set_idx, way, ready_ok = residency.lookup(seq.res,
                                                       needed_pages,
                                                       clock)
    local_hit = present & ready_ok
    slot = set_idx * seq.res.page.shape[-1] + way    # flat pool slot
    k_local, v_local = jax.tree.map(
        lambda pool: ops.paged_gather(pool, jnp.maximum(slot, 0)),
        (seq.kpool, seq.vpool))
    res = residency.touch(seq.res, set_idx, way, clock, pol,
                          gate=local_hit)
    res = residency.mark_dirty(res, set_idx, way, needed_writes,
                               gate=local_hit)
    return seq._replace(res=res), k_local, v_local, local_hit


def _transact(seqs: SeqState, cfg: KVStoreConfig, remote_k, remote_v,
              clock, pol: residency.PolicyFlags, needed_pages,
              needed_writes):
    """The fused residency transaction for B stacked sequences (leading
    batch axis on every SeqState leaf): one `ops.residency_fused` call
    executes landing + victim selection + dirty-eviction enqueue + pool
    scatter + CAM probe + hit gather + policy touch for the whole batch
    — `_land` + `_lookup` as ONE op (a single Pallas kernel on TPU,
    grid = batch; the fused jnp oracle elsewhere; `cfg.kernel_impl`
    picks). Only the engine CAM poll/retire and the stats fold stay
    outside: they are movement-plane state, not tier state.

    Returns (seqs', evicted (B, k), k_local, v_local, local_hit) with
    the same shapes/values as the vmapped legacy chain."""
    landed, landed_pages = jax.vmap(
        lambda e: poll_arrivals(e, clock))(seqs.eng)
    res, kpool, vpool, evicted, n_ev, k_local, v_local, local_hit = \
        ops.residency_fused(seqs.res, seqs.kpool, seqs.vpool, remote_k,
                            remote_v, landed, landed_pages, needed_pages,
                            needed_writes, clock, pol,
                            impl=cfg.kernel_impl)
    stats = {**seqs.stats,
             "evictions": seqs.stats["evictions"] + n_ev}
    eng = jax.vmap(
        lambda e: retire_arrivals(e, clock, cfg.daemon.lines_per_page))(
            seqs.eng)
    seqs = seqs._replace(res=res, kpool=kpool, vpool=vpool, eng=eng,
                         stats=stats)
    return seqs, evicted, k_local, v_local, local_hit


def _remote_fetch(remote_k, remote_v, pages_flat, any_miss):
    """Sub-block critical fetch from the remote tier for missed requests.

    `lax.cond` skips the gather entirely on 100%-hit steps (a real branch
    under jit and inside scan bodies — steady-state decode steps with a
    warm pool do zero remote reads). Otherwise every request's row is
    gathered (the `remote_rows` stat counts them). A latent tier passes
    `remote_v` None and gets None back."""
    tiers = (remote_k, remote_v)
    n = pages_flat.shape[0]

    def hit_path(_):
        return jax.tree.map(
            lambda t: jnp.zeros((n,) + tuple(t.shape[1:]), t.dtype), tiers)

    def miss_path(_):
        return jax.tree.map(lambda t: ops.paged_gather(t, pages_flat),
                            tiers)

    return jax.lax.cond(any_miss, miss_path, hit_path, None)


def _merge(local_hit, local, remote):
    """Served rows: the pool's where the request hit, the tier's
    elsewhere, for each page tensor of (k, v) (a None v stays None)."""
    def one(loc, rem):
        sel = local_hit.reshape(local_hit.shape
                                + (1,) * (rem.ndim - local_hit.ndim))
        return jnp.where(sel, loc.astype(rem.dtype), rem)
    return jax.tree.map(one, local, remote)


def _count_moves(before: SeqState, after: SeqState, rows) -> SeqState:
    """Add a step's `landings` (pages written into the pool: each evicts
    a resident page or fills an empty slot, and no slot empties again)
    and `remote_rows` (rows `_remote_fetch` gathered for the sequence) to
    its stats. Leaves may carry a batch axis."""
    empty = lambda s: jnp.sum(s.res.page < 0, axis=(-2, -1)).astype(F32)
    st = after.stats
    landed = (st["evictions"] - before.stats["evictions"] + empty(before)
              - empty(after))
    return after._replace(stats={**st,
                                 "landings": st["landings"] + landed,
                                 "remote_rows": st["remote_rows"] + rows})


# ---------------------------------------------------------- scheduling
def _writeback_offer(cfg: KVStoreConfig, clock, *, two_leg: bool,
                     cu=None, active=True):
    """The §4.3 offer of ONE dirty eviction, as a loop body
    `wb_one((eng, fab, nic), pid) -> ((eng', fab', nic'), wrote_back)`:
    the page goes to the dirty unit (`note_dirty_eviction`) and, when not
    buffered, is serialized on its module's writeback channel (and on
    unit `cu`'s NIC when `two_leg`). A `pid < 0` offer is an identity."""
    dp = cfg.daemon
    page_wire = _wire_bytes(cfg, cfg.page_tokens, cfg.compress_pages)

    if two_leg:
        def serve_wb(fab, nic, mc, gate):
            fab, nic, _ = compute_plane.serve_writeback_two_leg(
                fab, nic, mc, cu, clock, page_wire, gate=gate,
                active=active)
            return fab, nic
    else:
        def serve_wb(fab, nic, mc, gate):
            fab, _ = fabric.serve_writeback_at(fab, mc, clock, page_wire,
                                               gate=gate)
            return fab, nic

    def wb_one(carry, pid):
        eng, fab, nic = carry
        ok = pid >= 0
        mc = fabric.place(cfg.fabric, jnp.maximum(pid, 0))
        new_eng, buffered = note_dirty_eviction(eng, pid, dp)
        eng = _gate_tree(ok, eng, new_eng)
        wb = ok & ~buffered
        fab, nic = serve_wb(fab, nic, mc, wb)
        return (eng, fab, nic), wb

    return wb_one


def _offer_evictions(wb_one, carry, evicted):
    """Run `wb_one` over the valid (`>= 0`) entries of `evicted` (k,)
    int32, in their order, one iteration each: a step with no dirty
    eviction runs none. The -1 padding is skipped, not offered — an
    offer of -1 is an identity, so the result is the one a scan over all
    k entries gives. Returns (carry', n_wb, n_offers): the writebacks
    that went on the wire and the offers made."""
    k = evicted.shape[0]
    valid = evicted >= 0
    n_offers = jnp.sum(valid)
    # compact the valid entries to the front, order kept (no sort)
    rank = jnp.where(valid, jnp.cumsum(valid) - 1, k)
    packed = jnp.full_like(evicted, -1).at[rank].set(evicted, mode="drop")

    def body(i, c):
        carry, n_wb = c
        carry, wb = wb_one(carry, packed[i])
        return carry, n_wb + wb

    return (*jax.lax.fori_loop(0, n_offers, body,
                               (carry, jnp.zeros_like(n_offers))),
            n_offers)


def _request_order(cfg: KVStoreConfig, needed_pages, local_hit):
    """The requests `_schedule` visits, in order: (order, n, gap).

    A hit schedules nothing, has no stall and — under a static ratio —
    writes back the ratio it read; its one effect is its service call's
    decay of the fabric's offered-demand EMAs (`fabric.serve_dual_at`,
    read only by the adaptive controller). So where `cfg.adaptive_ratio`
    is off only the misses are visited: `order` (R+1,) holds their
    indices first, in request order (rank from `cumsum`, a scatter with
    `mode="drop"`, no sort), padded with R; `n` is their count; `gap`
    (R+1, M) int32 counts, by module, the hits before each visited miss
    since the previous one, and row `n` the hits after the last — the
    decays the skipped service calls owed. Under an adaptive ratio a hit
    moves the ratio, so every request is visited: (arange(R), R, None).
    """
    r = needed_pages.shape[0]
    if cfg.adaptive_ratio:
        return jnp.arange(r), r, None
    miss = ~local_hit
    n = jnp.sum(miss)
    rank = jnp.where(miss, jnp.cumsum(miss) - 1, r + 1)
    order = jnp.full((r + 1,), r, jnp.int32).at[rank].set(
        jnp.arange(r, dtype=jnp.int32), mode="drop")
    m = cfg.fabric.num_modules
    hit_on = local_hit[:, None] & (
        fabric.place(cfg.fabric, needed_pages)[:, None] == jnp.arange(m))
    # hits before each request, by module (row R: all of them)
    before = jnp.cumsum(
        jnp.concatenate([jnp.zeros((1, m), bool), hit_on]), axis=0,
        dtype=jnp.int32)
    seen = before[order]
    gap = seen - jnp.concatenate([jnp.zeros((1, m), jnp.int32), seen[:-1]])
    return order, n, gap


def _schedule(seq: SeqState, fab: FabricState, cfg: KVStoreConfig,
              needed_pages, needed_offsets, local_hit, clock, evicted,
              nic=None, cu=None, active=True):
    """Route every miss through the shared §4.2 selection unit and serve
    its transfers on the shared fabric (sequential within the step, so
    same-page requests dedup and queue exactly like the simulator).

    Arrival times are the fabric's `serve_dual` completions at the link
    bandwidth sampled at this decode step (time-varying under a scheduled
    `LinkModel`); the page's issue time is its transmission *start*
    (desim's `pn_start`), so a page queued behind a congested module can
    still be raced by lines. When `cfg.adaptive_ratio` is set, each
    request first nudges the target module's carried partition ratio
    toward the observed backlog/occupancy demand (`fabric.
    adapt_ratio_at`) — the serving side of the §4.1 repartitioning
    controller.

    The `stall_steps` stat accrues, per decode step, the *mean*
    per-request movement-plane delay (earliest of sub-block completion /
    inflight page arrival / own page completion, minus the clock; hit
    requests contribute zero) — the aggregate-latency metric
    `benchmarks/robustness.py` reports alongside the wire-lag makespan.

    `evicted` (k,) int32 (-1 padded) are this step's dirty pool
    evictions (from `_land`): each is offered to the §4.3 dirty unit
    (`note_dirty_eviction` — buffered if its page is inflight and under
    threshold, throttling past it) and, when not buffered, serialized on
    the victim page's module writeback channel. Only the valid entries
    are visited (`_offer_evictions`); the `writeback_offers` stat counts
    them.

    The request loop visits the misses only where the ratio is static
    (`_request_order`): a hit there changes nothing but the fabric's
    offered-demand EMAs, which decay by `(1 - EMA_ALPHA) ** hits` in
    closed form at each visited miss and after the last; every other
    leaf is the one a scan over all R requests gives. The
    `request_iterations` stat counts the visits.

    `nic`/`cu`/`active` switch on the compute plane's two-leg pricing:
    when a per-replica NIC bank is passed, every transfer (requests AND
    writebacks) also serializes on unit `cu`'s NIC channels with arrival
    = the later completion (`compute_plane.serve_dual_two_leg`). Returns
    (seq', fab', nic') — nic' is None on the single-endpoint path.
    """
    r = needed_pages.shape[0]
    dp = cfg.daemon
    nominal = float(page_cost_steps(cfg))
    line_wire = _wire_bytes(cfg, 1, False)            # critical token, raw
    page_wire = _wire_bytes(cfg, cfg.page_tokens, cfg.compress_pages)
    page_raw = _wire_bytes(cfg, cfg.page_tokens, False)

    if nic is None:
        def serve(fab, nic, mc, *, line_gate, page_gate):
            fab, line_done, page_done = fabric.serve_dual_at(
                fab, mc, partition=True, now=clock,
                line_ready=clock, line_bytes=line_wire,
                line_gate=line_gate,
                page_ready=clock, page_bytes=page_wire,
                page_gate=page_gate)
            return fab, nic, line_done, page_done, page_done
    else:
        def serve(fab, nic, mc, *, line_gate, page_gate):
            fab, nic, line_done, page_done, _, pd_mod = \
                compute_plane.serve_dual_two_leg(
                    fab, nic, mc, cu, partition=True, now=clock,
                    line_ready=clock, line_bytes=line_wire,
                    line_gate=line_gate,
                    page_ready=clock, page_bytes=page_wire,
                    page_gate=page_gate, active=active)
            return fab, nic, line_done, page_done, pd_mod

    # ---- dirty-eviction writebacks (pages written locally, now evicted:
    # §4.3 dirty unit first, writeback channel when not buffered) ----
    wb_one = _writeback_offer(cfg, clock, two_leg=nic is not None, cu=cu,
                              active=active)
    with jax.named_scope("store.writeback_scan"):
        (eng, fab, nic), n_wb, n_offers = _offer_evictions(
            wb_one, (seq.eng, fab, nic), evicted)

    def sched_one(carry, i):
        eng, fab, nic = carry
        pid = needed_pages[i]
        off = needed_offsets[i] % dp.lines_per_page
        mc = fabric.place(cfg.fabric, pid)
        bw = fabric.link_bw_at(fab.link, mc, clock)
        _, page_backlog = fabric.backlog(fab, mc, clock)
        pressure = page_backlog / (page_backlog + nominal)
        send_line, send_page = select_granularity(
            eng, pid, clock, selection_enabled=cfg.selection,
            always_both=not cfg.selection, module_pressure=pressure)
        fab = fabric.adapt_ratio_at(
            fab, mc, clock, adaptive=cfg.adaptive_ratio,
            r_idle=dp.bw_ratio, page_unit=page_wire,
            line_occ=utilization(eng.sb_key),
            page_occ=utilization(eng.page_key))
        _, page_share = bandwidth.shares(True, fab.ratio[mc])
        miss = ~local_hit[i]
        do_page = miss & send_page
        do_line = miss & send_line
        # inflight page the request can ride (lookup BEFORE scheduling)
        inflight, pidx = find(eng.page_key, pid)
        pending = jnp.where(inflight, eng.page_arrival[pidx], BIG)
        fab, nic, line_done, page_done, page_done_mod = serve(
            fab, nic, mc, line_gate=do_line, page_gate=do_page)
        # issue (left the page queue) = transmission start on the MODULE
        # channel — the §4.2 race window, as in desim's pn_start
        page_start = page_done_mod - page_wire / jnp.maximum(
            bw * page_share, 1e-6)
        eng = _gate_tree(do_page, eng,
                         schedule_page(eng, pid, page_start, page_done))
        eng = _gate_tree(do_line, eng,
                         schedule_line(eng, pid, off, line_done,
                                       dp.lines_per_page))
        served_at = jnp.minimum(jnp.where(do_line, line_done, BIG),
                                jnp.minimum(
                                    jnp.where(do_page, page_done, BIG),
                                    pending))
        served_at = jnp.where(served_at >= BIG / 2, clock + nominal,
                              served_at)
        stall = jnp.where(miss, jnp.maximum(served_at - clock, 0.0), 0.0)
        return (eng, fab, nic), (do_line, do_page, stall)

    def decay(carry, k):
        """The EMA decay that the hits of `gap` row k owed: by module on
        the fabric; on unit `cu`'s NIC once a hit, whatever its module."""
        eng, fab, nic = carry
        fab = fab._replace(line_rate=fab.line_rate * fade[k],
                           page_rate=fab.page_rate * fade[k])
        if nic is not None:
            f = fade_nic[k]
            nic = nic._replace(line_rate=nic.line_rate.at[cu].multiply(f),
                               page_rate=nic.page_rate.at[cu].multiply(f))
        return eng, fab, nic

    def visit(i, c):
        carry, n_sub, n_sched, stalls = c
        if gap is not None:
            carry = decay(carry, i)
        j = order[i]
        carry, (do_line, do_page, stall) = sched_one(carry, j)
        return (carry, n_sub + do_line, n_sched + do_page,
                stalls.at[j].set(stall))

    with jax.named_scope("store.request_scan"):
        order, n_visit, gap = _request_order(cfg, needed_pages, local_hit)
        if gap is not None:
            keep = jnp.float32(1.0 - fabric.EMA_ALPHA)
            fade = keep ** gap.astype(F32)
            fade_nic = keep ** jnp.sum(gap, axis=1).astype(F32)
        zero = jnp.zeros((), jnp.int32)
        (eng, fab, nic), n_sub, n_sched, stalls = jax.lax.fori_loop(
            0, n_visit, visit,
            ((eng, fab, nic), zero, zero, jnp.zeros((r,), F32)))
        if gap is not None:
            eng, fab, nic = decay((eng, fab, nic), n_visit)

    sub_bytes = n_sub * line_wire
    stt = seq.stats
    stats = {
        "sub_block_fetches": stt["sub_block_fetches"] + n_sub,
        "page_moves": stt["page_moves"] + n_sched,
        "wire_bytes": stt["wire_bytes"] + sub_bytes + n_sched * page_wire
        + n_wb * page_wire,
        "uncompressed_bytes": stt["uncompressed_bytes"] + sub_bytes
        + (n_sched + n_wb) * page_raw,
        "local_hits": stt["local_hits"] + jnp.sum(local_hit),
        "requests": stt["requests"] + r,
        # aggregate movement-plane delay: mean per-request stall this step
        "stall_steps": stt["stall_steps"] + jnp.mean(stalls),
        "writeback_bytes": stt["writeback_bytes"] + n_wb * page_wire,
        "dirty_evicts": stt["dirty_evicts"] + n_wb,
        # accrued in the transaction: evictions, landings, remote_rows
        "evictions": stt["evictions"],
        "landings": stt["landings"],
        "remote_rows": stt["remote_rows"],
        "writeback_offers": stt["writeback_offers"] + n_offers,
        "request_iterations": stt["request_iterations"] + n_visit,
    }

    # ---- telemetry plane (DESIGN.md §10): recorded HERE, at the oracle
    # boundary outside the fused residency kernel — stalls/hits/fabric
    # state are stepper-level values, so the histogram and series are
    # identical across every `kernel_impl` by construction ----
    tel = seq.tel
    tcfg = cfg.telemetry
    if tel is not None and tcfg.enabled:
        # per-request service lag in decode steps; hit requests
        # contribute stall 0 (clamped into bin 0, "served now")
        tel = telemetry.record_latency(tel, tcfg, stalls)
        step_i = (clock - 1.0).astype(jnp.int32)
        tel = telemetry.record_series(
            tel, tcfg, step_i,
            jnp.stack([
                jnp.mean(jnp.maximum(fab.page_busy - clock, 0.0)),
                jnp.mean(fab.ratio),
                jnp.mean(local_hit.astype(F32)),
                stats["evictions"],
                stats["writeback_bytes"],
                jnp.mean(fabric.module_health(fab.link, clock)),
            ]))
    return seq._replace(eng=eng, stats=stats, tel=tel), fab, nic


def _offsets_or_zero(needed_pages, needed_offsets):
    if needed_offsets is None:
        return jnp.zeros(needed_pages.shape, jnp.int32)
    return jnp.asarray(needed_offsets, jnp.int32)


def _writes_or_zero(needed_pages, needed_writes):
    if needed_writes is None:
        return jnp.zeros(needed_pages.shape, bool)
    return jnp.asarray(needed_writes, bool)


def _policy_or_cfg(cfg: KVStoreConfig, policy) -> residency.PolicyFlags:
    """The steppers' replacement policy: `cfg.policy` by default, or a
    TRACED override (PolicyFlags / PolicySpec / name) — policy flags are
    data in the compiled step, so a policy sweep over one static config
    reuses a single compile (the desim `policies=` lattice pattern)."""
    return (cfg.policy_flags() if policy is None
            else residency.as_policy(policy))


# ------------------------------------------------------------- steppers
def step_fetch(state: KVStoreState, cfg: KVStoreConfig,
               remote_k, remote_v, needed_pages, needed_offsets=None,
               needed_writes=None, policy=None):
    """Serve one decode step needing `needed_pages` (R,) page ids.

    `needed_offsets` (R,) are the requests' token offsets within their
    pages — the sub-block plane keys on the same packed (page<<6|off)
    the simulator uses, so repeat touches of one token dedup while
    distinct tokens of one page race independently. Defaults to offset 0.
    `needed_writes` (R,) bool marks requests that WRITE their page (the
    KV append of the current decode position): a written resident page
    turns dirty and owes a writeback when later evicted. Defaults to
    all-False (read-only — the pre-writeback-path behavior, unchanged).
    `policy` optionally overrides `cfg.policy` with TRACED flags
    (`_policy_or_cfg`) — a policy sweep reuses one compile per config.

    Returns (state, k (R,page,KV,D), v, served_local (R,) bool). A latent
    store (`cfg.latent_dim`) passes its one tier as `remote_k` with
    `remote_v=None` and serves k (R, *cfg.page_row()) with v None.
    Misses are served via the sub-block plane from the remote tier now;
    page migrations drain through the shared fabric's per-module page
    channels and land when their (possibly congested) service completes.
    A miss whose page is already inflight and issued moves no extra wire
    bytes — the request rides the page already in flight (exactly the
    simulator's race rule).
    """
    needed_pages = jnp.asarray(needed_pages, jnp.int32)
    r = needed_pages.shape[0]
    offs = _offsets_or_zero(needed_pages, needed_offsets)
    writes = _writes_or_zero(needed_pages, needed_writes)
    pol = _policy_or_cfg(cfg, policy)
    clock = state.clock + 1.0
    with jax.named_scope("store.transact"):
        if cfg.kernel_impl == "chain":
            seq, evicted = _land(state.seq, cfg, remote_k, remote_v,
                                 clock, pol)
            seq, k_local, v_local, local_hit = _lookup(seq, clock,
                                                       needed_pages,
                                                       writes, pol)
        else:
            seqs = jax.tree.map(lambda x: x[None], state.seq)
            out = _transact(seqs, cfg, remote_k, remote_v, clock, pol,
                            needed_pages[None], writes[None])
            seq, evicted, k_local, v_local, local_hit = jax.tree.map(
                lambda x: x[0], out)
    with jax.named_scope("store.serve"):
        any_miss = jnp.any(~local_hit)
        remote = _remote_fetch(remote_k, remote_v, needed_pages, any_miss)
        k, v = _merge(local_hit, (k_local, v_local), remote)
        seq = _count_moves(state.seq, seq, (any_miss * r).astype(F32))
    with jax.named_scope("store.schedule_fold"):
        seq, fab, _ = _schedule(seq, state.fab, cfg, needed_pages, offs,
                                local_hit, clock, evicted)
    return KVStoreState(seq=seq, fab=fab, clock=clock), k, v, local_hit


def step_fetch_batch(state: BatchedKVStoreState, cfg: KVStoreConfig,
                     remote_k, remote_v, needed_pages, needed_offsets=None,
                     needed_writes=None, policy=None):
    """Serve one decode step for a whole batch: `needed_pages` (B, R).

    Landing, lookup and the local serve are `vmap`ped across the B
    sequences; the remote critical fetch is one batch-level gather
    (skipped entirely when every request in the batch hits); scheduling
    folds over the batch in sequence order with the ONE shared fabric as
    carry — so tenants contend for the same per-module channels and a
    hot module delays every sequence's landings, deterministically.

    Returns (state, k (B,R,page,KV,D), v, served_local (B,R) bool); a
    latent store as in `step_fetch` (k (B,R,*cfg.page_row()), v None).
    """
    needed_pages = jnp.asarray(needed_pages, jnp.int32)
    b, r = needed_pages.shape
    offs = _offsets_or_zero(needed_pages, needed_offsets)
    writes = _writes_or_zero(needed_pages, needed_writes)
    pol = _policy_or_cfg(cfg, policy)
    clock = state.clock + 1.0
    with jax.named_scope("store.transact"):
        if cfg.kernel_impl == "chain":
            seqs, evicted = jax.vmap(
                lambda s: _land(s, cfg, remote_k, remote_v, clock, pol))(
                    state.seqs)
            seqs, k_local, v_local, local_hit = jax.vmap(
                lambda s, need, wr: _lookup(s, clock, need, wr, pol))(
                    seqs, needed_pages, writes)
        else:
            seqs, evicted, k_local, v_local, local_hit = _transact(
                state.seqs, cfg, remote_k, remote_v, clock, pol,
                needed_pages, writes)
    with jax.named_scope("store.serve"):
        any_miss = jnp.any(~local_hit)
        remote = jax.tree.map(
            lambda x: x.reshape((b, r) + tuple(x.shape[1:])),
            _remote_fetch(remote_k, remote_v, needed_pages.reshape(-1),
                          any_miss))
        k, v = _merge(local_hit, (k_local, v_local), remote)
        seqs = _count_moves(state.seqs, seqs, (any_miss * r).astype(F32))

    def sched_seq(fab, xs):
        seq, need, off, hit, ev = xs
        seq, fab, _ = _schedule(seq, fab, cfg, need, off, hit, clock, ev)
        return fab, seq

    with jax.named_scope("store.schedule_fold"):
        fab, seqs = jax.lax.scan(sched_seq, state.fab,
                                 (seqs, needed_pages, offs, local_hit,
                                  evicted))
    return (BatchedKVStoreState(seqs=seqs, fab=fab, clock=clock),
            k, v, local_hit)


def step_fetch_replicated(state: ReplicatedKVStoreState,
                          cfg: KVStoreConfig, remote_k, remote_v,
                          needed_pages, needed_offsets=None,
                          needed_writes=None, policy=None, active=None):
    """Serve one decode step for C replicas x B tenants:
    `needed_pages` (C, B, R) (replica-major, matching the state layout).

    Landing / lookup / local serve are `vmap`ped across all C*B
    sequences and the remote critical fetch is one gather, exactly like
    `step_fetch_batch`; scheduling folds over the sequences in
    replica-major order with BOTH banks as carry — the shared memory-side
    fabric (all replicas queue on the same per-module channels) and the
    per-replica NIC bank (each replica's transfers additionally
    serialize on its own ingress, arrival = the later completion). With
    C == 1 the NIC leg is gated off and this is `step_fetch_batch`.

    `active` overrides the NIC gate (default: C > 1 from the local
    shape). The mesh plane (`runtime/mesh_plane.py`) passes the GLOBAL
    replica count's gate when each device steps a local slice whose own
    C may be 1 — the gate must reflect the whole deployment, not the
    shard.

    Returns (state, k (C,B,R,page,KV,D), v, served_local (C,B,R) bool).
    """
    c, b, r = needed_pages.shape
    flat = needed_pages.reshape((c * b, r))
    offs = _offsets_or_zero(flat, None if needed_offsets is None
                            else jnp.asarray(needed_offsets).reshape(
                                (c * b, r)))
    writes = _writes_or_zero(flat, None if needed_writes is None
                             else jnp.asarray(needed_writes).reshape(
                                 (c * b, r)))
    cus = jnp.arange(c * b, dtype=jnp.int32) // b    # owning replica
    active = (c > 1) if active is None else active
    pol = _policy_or_cfg(cfg, policy)
    clock = state.clock + 1.0
    with jax.named_scope("store.transact"):
        if cfg.kernel_impl == "chain":
            seqs, evicted = jax.vmap(
                lambda s: _land(s, cfg, remote_k, remote_v, clock, pol))(
                    state.seqs)
            seqs, k_local, v_local, local_hit = jax.vmap(
                lambda s, need, wr: _lookup(s, clock, need, wr, pol))(
                    seqs, flat, writes)
        else:
            seqs, evicted, k_local, v_local, local_hit = _transact(
                state.seqs, cfg, remote_k, remote_v, clock, pol, flat,
                writes)
    with jax.named_scope("store.serve"):
        any_miss = jnp.any(~local_hit)
        remote = jax.tree.map(
            lambda x: x.reshape((c * b, r) + tuple(x.shape[1:])),
            _remote_fetch(remote_k, remote_v, flat.reshape(-1), any_miss))
        k, v = _merge(local_hit, (k_local, v_local), remote)
        seqs = _count_moves(state.seqs, seqs, (any_miss * r).astype(F32))

    def sched_seq(carry, xs):
        fab, nic = carry
        seq, need, off, hit, ev, cu = xs
        seq, fab, nic = _schedule(seq, fab, cfg, need, off, hit, clock,
                                  ev, nic=nic, cu=cu, active=active)
        return (fab, nic), seq

    with jax.named_scope("store.schedule_fold"):
        (fab, nic), seqs = jax.lax.scan(
            sched_seq, (state.fab, state.nic),
            (seqs, flat, offs, local_hit, evicted, cus))
    k, v = jax.tree.map(lambda x: x.reshape((c, b, r) + x.shape[2:]),
                        (k, v))
    return (ReplicatedKVStoreState(seqs=seqs, fab=fab, nic=nic,
                                   clock=clock),
            k, v, local_hit.reshape((c, b, r)))


def ledger(state) -> dict:
    """Python-side movement summary: stats totals (summed over the batch
    for a Batched/ReplicatedKVStoreState) + the fabric's per-module wire
    bytes (+ per-unit NIC bytes for a replicated store). When the
    telemetry plane is on (`SeqState.tel` present), the batch-summed
    stall histogram adds tail percentiles — `stall_p50_steps` /
    `stall_p90_steps` / `stall_p99_steps` (self-contained: the bin edges
    ride in the state, no config needed)."""
    seq = state.seq if isinstance(state, KVStoreState) else state.seqs
    out = {k: float(jnp.sum(v)) for k, v in seq.stats.items()}
    if seq.tel is not None:
        p50, p90, p99 = telemetry.percentiles_from_state(
            seq.tel, [0.5, 0.9, 0.99])
        out["stall_p50_steps"] = p50
        out["stall_p90_steps"] = p90
        out["stall_p99_steps"] = p99
    fab = state.fab
    out["module_bytes"] = [
        float(x) for x in fab.line_bytes + fab.page_bytes + fab.wb_bytes]
    if isinstance(state, ReplicatedKVStoreState):
        out["unit_bytes"] = [
            float(x) for x in compute_plane.unit_bytes(state.nic)]
    return out
