"""The store holding MLA latent pages (`KVStoreConfig.latent_dim`): one
page tensor, on seeded tier rows at a small size.

Served rows and resident pool rows equal the tier's rows; hit flags,
page tables and the integer ledger equal the plain reference's replay
(`bench/reference/store_latent_ref.py`); the three steppers and both
transaction paths agree; `landings` and `remote_rows` equal counts
recomputed from the request stream."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daemon_store as ds
from repro.core.fabric import FabricConfig
from repro.core.params import DaemonParams

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import remote_tier  # noqa: E402
import traffic  # noqa: E402
from reference import store_latent_ref  # noqa: E402

DSV3 = json.loads((BENCH / "configs" / "kv-tier-deepseek-v3.json")
                  .read_text())
PAGES = 64                    # pages a store: a 4096-token context
LATENT = DSV3["kv_lora_rank"] + DSV3["qk_rope_head_dim"]      # 576
SALT = remote_tier.seed_words(2**31 + 3, 0)


def _spec_cfg(pool, ways):
    """The DeepSeek-V3 configuration at a test's size: a 4096-token
    context, a `pool`-slot pool of `ways` ways."""
    return dict(DSV3, store=dict(DSV3["store"], context_tokens=PAGES * 64,
                                 local_pages_per_tenant=pool,
                                 pool_ways=ways))


def _kcfg(cfg, impl="ref"):
    st = cfg["store"]
    return ds.KVStoreConfig(
        num_local_pages=st["local_pages_per_tenant"],
        page_tokens=st["page_tokens"], latent_dim=LATENT,
        daemon=DaemonParams(**cfg["daemon"]),
        compress_pages=st["compress_pages"],
        page_budget_per_step=st["page_budget_per_step"],
        fabric=FabricConfig(num_modules=st["memory_modules"],
                            placement=st["placement"],
                            affinity_block=PAGES),
        policy=st["policy"], pool_ways=st["pool_ways"], kernel_impl=impl)


def _streams(mix, stores, steps, seed=5):
    """(T, stores, R) pages, offsets, writes. `full`: every page of the
    context newest first, appending to the newest (the cell's mix);
    `random`: zipf-skewed reads with writes anywhere in a page."""
    if mix == "full":
        m = json.loads((BENCH / "traffic" / "fullctx-b8.json").read_text())
        m.update(tenants=stores, stream_steps=steps, requests_per_step=PAGES,
                 start_min=PAGES * 64 - 64)
        return traffic.store_streams(m, PAGES, 64, seed)
    rng = np.random.default_rng(seed)
    shape = (steps, stores, 8)
    rank = rng.zipf(1.3, size=shape).clip(1, PAGES)
    pages = rank - 1 + (np.arange(stores) * PAGES)[None, :, None]
    offs = rng.integers(0, 64, size=shape).astype(np.int32)
    return pages.astype(np.int32), offs, rng.random(shape) < 0.3


def _tier(kcfg, stores):
    ids = jnp.arange(stores * PAGES, dtype=jnp.uint32)
    return remote_tier.rows(ids, kcfg.page_row(), SALT, jnp)


def _drive(kcfg, streams, stepper="batch"):
    pages, offs, writes = streams
    b = pages.shape[1]
    tier = _tier(kcfg, b)
    if stepper == "batch":
        st = ds.init_kv_store_batch(kcfg, b)
        step = jax.jit(lambda s, p, o, w: ds.step_fetch_batch(
            s, kcfg, tier, None, p, o, w))
    else:
        st = ds.init_kv_store_replicated(kcfg, 1, b)

        @jax.jit
        def step(s, p, o, w):
            s, k, v, h = ds.step_fetch_replicated(s, kcfg, tier, None,
                                                  p[None], o[None], w[None])
            return s, k[0], v, h[0]
    hits, served = [], []
    for t in range(pages.shape[0]):
        st, k, v, h = step(st, pages[t], offs[t], writes[t])
        assert v is None
        hits.append(np.asarray(h))
        served.append(k)
    return st, np.stack(hits), served, tier


@pytest.mark.parametrize("mix,pool,ways", [("full", 12, 4),
                                           ("random", 12, 4),
                                           ("random", 16, 16)])
def test_latent_store_matches_reference(mix, pool, ways):
    cfg = _spec_cfg(pool, ways)
    kcfg = _kcfg(cfg)
    streams = _streams(mix, 3, 10)
    st, hits, served, tier = _drive(kcfg, streams)
    pages = streams[0]
    # served rows and resident pool rows are the tier's rows
    for t, k in enumerate(served):
        assert k.shape == pages[t].shape + (288, 128)
        np.testing.assert_array_equal(np.asarray(k), np.asarray(tier)[
            pages[t]])
    table = np.asarray(st.seqs.res.page).reshape(3, -1)
    pool_rows = np.asarray(st.seqs.kpool)
    live = table >= 0
    np.testing.assert_array_equal(pool_rows[live],
                                  np.asarray(tier)[table[live]])
    assert st.seqs.vpool is None
    # hits, tables and the integer ledger are the reference's
    spec = store_latent_ref.StoreSpec(cfg, 3)
    ref = store_latent_ref.run(spec, *streams)
    np.testing.assert_array_equal(hits, ref["hits"])
    np.testing.assert_array_equal(np.asarray(st.seqs.res.page),
                                  ref["tables"])
    led = ds.ledger(st)
    for key in store_latent_ref.LEDGER_KEYS:
        assert led[key] == ref["ledger"][key], key
    assert led["module_bytes"] == ref["module_bytes"]
    # the miss path did the work: pages landed and were evicted
    assert led["evictions"] > 0 and led["landings"] > led["evictions"]
    if mix == "full":
        assert led["dirty_evicts"] > 0 and led["writeback_offers"] > 0


@pytest.mark.parametrize("mix", ["full", "random"])
def test_latent_move_counters_match_stream(mix):
    """`landings` and `remote_rows` equal what the reference's replay of
    the same stream implies: a landing is a pool slot whose page changed
    in a step (a page lands only where it is not resident), and the
    remote fetch gathers every request of a step in which any request
    of the batch missed."""
    cfg = _spec_cfg(12, 4)
    streams = _streams(mix, 3, 10, seed=9)
    st, _, _, _ = _drive(_kcfg(cfg), streams)
    led = ds.ledger(st)
    spec = store_latent_ref.StoreSpec(cfg, 3)
    landings, prev = 0, None
    for t in range(1, streams[0].shape[0] + 1):
        ref = store_latent_ref.run(spec, *(x[:t] for x in streams))
        tables = ref["tables"]
        landings += int(np.sum(tables != (-1 if prev is None else prev)))
        prev = tables
    missed_steps = np.sum(~ref["hits"].all(axis=(1, 2)))
    r = streams[0].shape[1] * streams[0].shape[2]
    assert led["landings"] == float(landings) > 0
    assert led["remote_rows"] == float(r * missed_steps) > 0


@pytest.mark.parametrize("mix", ["full", "random"])
def test_latent_steppers_agree(mix):
    """B=1 `step_fetch` equals `step_fetch_batch` (state, rows, hits),
    the C=1 replicated stepper equals the batched one, and the legacy
    chain equals the fused transaction, for the latent layout."""
    cfg = _spec_cfg(12, 4)
    pages, offs, writes = _streams(mix, 1, 8)
    kcfg = _kcfg(cfg)
    tier = _tier(kcfg, 1)
    single = ds.init_kv_store(kcfg)
    step1 = jax.jit(lambda s, p, o, w: ds.step_fetch(s, kcfg, tier, None,
                                                     p, o, w))
    s_b, hits_b, served_b, _ = _drive(kcfg, (pages, offs, writes))
    for t in range(pages.shape[0]):
        single, k, v, h = step1(single, pages[t, 0], offs[t, 0],
                                writes[t, 0])
        assert v is None
        np.testing.assert_array_equal(np.asarray(k),
                                      np.asarray(served_b[t][0]))
        np.testing.assert_array_equal(np.asarray(h), hits_b[t, 0])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)[0]), single.seq, s_b.seqs)
    np.testing.assert_array_equal(np.asarray(single.fab.page_bytes),
                                  np.asarray(s_b.fab.page_bytes))
    for other in (_drive(kcfg, (pages, offs, writes), "replicated"),
                  _drive(_kcfg(cfg, "chain"), (pages, offs, writes))):
        s_o, hits_o, served_o, _ = other
        np.testing.assert_array_equal(hits_o, hits_b)
        for a, b in zip(served_o, served_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_o.seqs, s_b.seqs)


def test_latent_layout_bytes_and_shapes():
    """A latent page costs `latent_dim` bfloat16 values a token on the
    link (1152 B for DeepSeek-V3, against 8 KiB of K+V for OLMoE's 16
    heads x 128), and is held as whole (16, 128) tiles."""
    kcfg = _kcfg(_spec_cfg(12, 4))
    assert ds._token_bytes(kcfg) == 1152.0
    assert kcfg.page_row() == (288, 128)
    kv = ds.KVStoreConfig(num_local_pages=4, page_tokens=16, kv_heads=16,
                          head_dim=128)
    assert ds._token_bytes(kv) == 8192.0
    assert kv.page_row() == (16, 16, 128)
    assert ds.link_bytes_per_step(kcfg) == pytest.approx(
        36287 * 1152 / 0.75)
    st = ds.init_kv_store_batch(kcfg, 2)
    assert st.seqs.kpool.shape == (2, 12, 288, 128)
    assert st.seqs.vpool is None


@pytest.mark.parametrize("kw", [
    dict(latent_dim=576, kv_heads=1, head_dim=576),   # both layouts
    dict(latent_dim=500),                             # not whole tiles
    dict(kv_heads=0, head_dim=128),                   # no layout
])
def test_latent_config_rejects_bad_layouts(kw):
    with pytest.raises(ValueError):
        ds.KVStoreConfig(num_local_pages=4, page_tokens=64, **kw)
