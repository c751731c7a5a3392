"""DaeMon paged-KV serving: generation + movement-ledger comparison.

Runs batched decode with the two-tier DaemonKVStore handling KV page
residency — B tenant sequences against M memory modules on ONE movement
fabric (`repro.core.fabric`) — twice: once DaeMon-style (critical
sub-block fetches + compressed page migrations + adaptive selection) and
once Remote-style (uncompressed page-only movement), and reports wire
bytes + hit ratios per tenant and per module — the serving analogue of
paper fig 8/17/19.

The store's movement plane is the same `repro.core.engine` selection +
inflight machinery and the same `fabric.serve_dual_at` channel service
the simulator uses: page arrival times are real (possibly congested)
channel completions, a miss whose page is already inflight and issued
rides the in-flight page (§4.2 race rule), and a hot module delays every
tenant's landings.

  PYTHONPATH=src python examples/serve_paged.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import telemetry
from repro.core.daemon_store import (SERIES_CHANNELS, KVStoreConfig,
                                     init_kv_store_batch, ledger,
                                     link_bytes_per_step,
                                     step_fetch_batch)
from repro.core.fabric import FabricConfig, scheduled_link
from repro.runtime import obs
from repro.runtime.fault import LinkHealthMonitor
from repro.sim.workloads import make_link_schedule
from repro.models.model import ModelOptions, init_model
from repro.runtime.serve_loop import (PagedServeConfig, ServeConfig,
                                      serve_batch_paged, serve_replicated)

BATCH = 4
MODULES = 4


def kv_movement_ledger(compress: bool, steps: int = 120,
                       placement: str = "interleave"):
    """Replay zipf page-access streams for BATCH tenants through the
    two-tier store sharing one MODULES-wide fabric."""
    cfg = KVStoreConfig(num_local_pages=16, page_tokens=16, kv_heads=4,
                        head_dim=64, compress_pages=compress,
                        page_budget_per_step=8,
                        fabric=FabricConfig(num_modules=MODULES,
                                            placement=placement))
    state = init_kv_store_batch(cfg, BATCH)
    key = jax.random.PRNGKey(0)
    remote_k = jax.random.normal(key, (64, 16, 4, 64), jnp.float32)
    remote_v = jax.random.normal(jax.random.fold_in(key, 1),
                                 (64, 16, 4, 64), jnp.float32)
    rng = np.random.default_rng(0)
    pages = (rng.zipf(1.4, size=(steps, BATCH, 4)).clip(1, 64) - 1).astype(
        np.int32)
    offs = rng.integers(0, 16, size=(steps, BATCH, 4)).astype(np.int32)
    fetch = jax.jit(lambda st, need, off: step_fetch_batch(
        st, cfg, remote_k, remote_v, need, off))
    for t in range(steps):
        state, k, v, hit = fetch(state, jnp.asarray(pages[t]),
                                 jnp.asarray(offs[t]))
    return ledger(state)


def tenant_capacity_demo(steps: int = 120):
    """Residency-plane demo: one capacity-SQUEEZED tenant (hot set spans
    the whole remote region, far beyond its pool) and one ROOMY tenant
    (hot set fits the pool) share ONE movement fabric. The unified
    per-tenant residency stats separate their fates: the squeezed tenant
    churns (evictions, dirty writebacks, low hit ratio) while the roomy
    tenant converges to ~all hits — and both contend for the same
    per-module channels."""
    cfg = KVStoreConfig(num_local_pages=8, page_tokens=16, kv_heads=4,
                        head_dim=64, page_budget_per_step=8,
                        policy="lru",  # swap for any residency.POLICIES
                        fabric=FabricConfig(num_modules=2),
                        # full telemetry plane: per-tenant stall
                        # histograms + series ring (host spans come
                        # from the recorder below)
                        telemetry=telemetry.TelemetryConfig(
                            level="histogram", lat_lo=0.01, lat_hi=1e4))
    state = init_kv_store_batch(cfg, 2)
    remote = jnp.zeros((128, 16, 4, 64), jnp.bfloat16)
    rng = np.random.default_rng(0)
    # tenant 0: zipf over its full 64-page region (8-slot pool: squeezed)
    squeezed = (rng.zipf(1.3, size=(steps, 4)).clip(1, 64) - 1)
    # tenant 1: the same stream folded into 8 hot pages (pool-resident)
    roomy = squeezed % 8 + 64
    pages = np.stack([squeezed, roomy], axis=1).astype(np.int32)
    offs = rng.integers(0, 16, size=(steps, 2, 4)).astype(np.int32)
    # every request appends KV (write): resident pages turn dirty, so
    # the squeezed tenant's churn owes writebacks on the reverse channel
    writes = np.ones((steps, 2, 4), bool)
    fetch = jax.jit(lambda st, need, off, wr: step_fetch_batch(
        st, cfg, remote, remote, need, off, wr))
    rec = obs.SpanRecorder()
    with rec.span("tenant_replay", steps=steps) as sp:
        for t in range(steps):
            state, *_ = fetch(state, jnp.asarray(pages[t]),
                              jnp.asarray(offs[t]), jnp.asarray(writes[t]))
        sp["sync"] = state.fab.page_busy
    stats = state.seqs.stats             # per-tenant (B,) leaves
    print(f"\n== residency plane: capacity-squeezed vs roomy tenant "
          f"(pool=8 slots each, policy={cfg.policy}, shared fabric) ==")
    for b, name in ((0, "squeezed (64-page hot set)"),
                    (1, "roomy    (8-page hot set)")):
        hits = float(stats["local_hits"][b])
        reqs = float(stats["requests"][b])
        print(f"  tenant {b} {name}: evictions={stats['evictions'][b]:.0f} "
              f"dirty_evicts={stats['dirty_evicts'][b]:.0f} "
              f"writeback={float(stats['writeback_bytes'][b])/1e3:.1f}KB "
              f"hit={hits / max(reqs, 1):.2f}")
    led = ledger(state)
    print(f"  shared fabric: wire={led['wire_bytes']/1e6:.2f}MB "
          f"per-module MB="
          f"{'/'.join(f'{b/1e6:.2f}' for b in led['module_bytes'])}")
    print(f"  tail: stall p50={led['stall_p50_steps']:.3g} "
          f"p90={led['stall_p90_steps']:.3g} "
          f"p99={led['stall_p99_steps']:.3g} decode steps (both tenants)")
    print(obs.summary("squeezed-vs-roomy tenants", state.seqs.tel,
                      cfg.telemetry, SERIES_CHANNELS, unit="steps"))
    # Perfetto export: the replay span over per-tenant counter tracks
    # (synthetic steps-as-ms timebase) — drag onto ui.perfetto.dev
    counters = []
    for b, pid in ((0, 1), (1, 2)):
        t0 = jax.tree.map(lambda x: x[b], state.seqs.tel)
        counters += obs.counter_events(t0, cfg.telemetry,
                                       SERIES_CHANNELS, pid=pid)
    obs.trace_export("TRACE_tenants.json", spans=rec.events,
                     counters=counters,
                     metadata={"tenant-replay": 0, "tenant-0 squeezed": 1,
                               "tenant-1 roomy": 2})
    print("  trace written: TRACE_tenants.json (ui.perfetto.dev)")


def main():
    print(f"== generation with paged-KV movement plane "
          f"(reduced qwen3-1.7b, B={BATCH}, M={MODULES}) ==")
    cfg = get_config("qwen3-1.7b").reduced()
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (BATCH, 6), 2, 200,
                                 jnp.int32)
    store_cfg = KVStoreConfig(
        num_local_pages=8, page_tokens=4, kv_heads=2, head_dim=32,
        page_budget_per_step=4, adaptive_ratio=True,
        fabric=FabricConfig(num_modules=MODULES, placement="affinity",
                            affinity_block=8))
    # time-varying link: module 0's health flaps to near-dead mid-decode
    # (knot times are decode steps); the health monitor watches it and
    # surfaces a reshard advisory in the ledger
    n_steps = 6 + 10
    link = scheduled_link(
        link_bytes_per_step(store_cfg),
        make_link_schedule("flap", float(n_steps), MODULES, knots=8),
        MODULES)
    out, led = serve_batch_paged(params, cfg, prompts,
                                 ServeConfig(max_new_tokens=10), store_cfg,
                                 PagedServeConfig(window_pages=2,
                                                  pages_per_seq=8),
                                 link=link,
                                 health_monitor=LinkHealthMonitor(
                                     patience=2))
    for row in out:
        print("  gen:", row.tolist())
    hr = led["local_hits"] / max(led["requests"], 1)
    print(f"  decode movement: wire={led['wire_bytes']/1e3:.1f}KB "
          f"pages={led['page_moves']:.0f} "
          f"sub_blocks={led['sub_block_fetches']:.0f} hit={hr:.2f} "
          f"reshard_advised={led['link_reshard_modules']}")

    print(f"\n== DaeMon KV movement ledger vs Remote-style "
          f"(B={BATCH} tenants x M={MODULES} modules) ==")
    daemon = kv_movement_ledger(compress=True)
    remote = kv_movement_ledger(compress=False)
    for name, led in (("daemon", daemon), ("remote-style", remote)):
        hr = led["local_hits"] / max(led["requests"], 1)
        per_mod = "/".join(f"{b/1e6:.2f}" for b in led["module_bytes"])
        print(f"  {name:13s} wire={led['wire_bytes']/1e6:7.2f}MB "
              f"(raw {led['uncompressed_bytes']/1e6:7.2f}MB) "
              f"pages={led['page_moves']:.0f} "
              f"sub_blocks={led['sub_block_fetches']:.0f} hit={hr:.2f} "
              f"per-module MB={per_mod}")
    saving = 1 - daemon["wire_bytes"] / remote["wire_bytes"]
    print(f"  => DaeMon moves {saving*100:.1f}% fewer wire bytes at equal "
          "service (compressed page plane + critical sub-blocks)")

    tenant_capacity_demo()

    print("\n== replicated serving: C=2 replicas contending on ONE hot "
          "module ==")
    # one memory module = every replica's page migrations queue on the
    # same channel; each replica still owns its NIC bank, so the ledger
    # separates per-module (shared) from per-unit (replicated) bytes
    cfg2 = get_config("qwen3-1.7b").reduced()
    params2, _ = init_model(jax.random.PRNGKey(2), cfg2)
    prompts2 = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 2, 200,
                                  jnp.int32)
    # small pool + short pages: the decode's KV-append window outgrows
    # the pool, so locally-written pages get evicted and pay writebacks
    rep_cfg = KVStoreConfig(
        num_local_pages=4, page_tokens=2, kv_heads=2, head_dim=32,
        page_budget_per_step=2,
        fabric=FabricConfig(num_modules=1))      # the hot shared module
    toks, led = serve_replicated(params2, cfg2, prompts2,
                                 ServeConfig(max_new_tokens=10), rep_cfg,
                                 num_replicas=2,
                                 pcfg=PagedServeConfig(window_pages=2,
                                                       pages_per_seq=8))
    hr = led["local_hits"] / max(led["requests"], 1)
    print(f"  tokens: {toks.shape} (C, B, P+new)")
    print(f"  wire={led['wire_bytes']/1e3:.1f}KB "
          f"writebacks={led['writeback_bytes']/1e3:.1f}KB hit={hr:.2f}")
    print(f"  shared module KB: "
          f"{'/'.join(f'{b/1e3:.1f}' for b in led['module_bytes'])}  "
          f"per-replica NIC KB: "
          f"{'/'.join(f'{b/1e3:.1f}' for b in led['unit_bytes'])}")


if __name__ == "__main__":
    main()
