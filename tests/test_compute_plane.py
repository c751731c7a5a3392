"""The compute plane (`repro.core.compute_plane`): two-leg service
semantics, request->unit sharding, the C=1 bit-identity pin against the
seed golden (idle NIC banks), single-compile behavior of the schemes x
compute-unit lattice, two-endpoint byte conservation (per-unit NIC
ledgers == per-module ledgers == caller totals) for desim and the
replicated serving store, and the serving-store writeback path."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compute_plane, fabric
from repro.core.compute_plane import (init_nic_bank, nic_link_for,
                                      serve_dual_two_leg,
                                      serve_writeback_two_leg, shard_unit,
                                      unit_bytes)
from repro.core.daemon_store import (KVStoreConfig, init_kv_store_batch,
                                     init_kv_store_replicated, ledger,
                                     step_fetch, step_fetch_batch,
                                     step_fetch_replicated)
from repro.core.fabric import FabricConfig
from repro.core.params import NetworkParams
from repro.sim.desim import (SimConfig, lattice_cache_size, make_net,
                             run_trace, simulate_lattice)
from repro.sim.schemes import SCHEMES
from repro.sim.trace import generate_trace
from repro.sim.workloads import WORKLOADS

GOLDEN = Path(__file__).parent / "golden" / "seed_movement_golden.json"


# ------------------------------------------------------- two-leg service
def test_two_leg_inactive_is_module_leg_and_idle_nic():
    """active=False: combined completions == module completions and the
    NIC bank is untouched (clocks AND ledgers) — the C=1 seed path."""
    mem = fabric.init_fabric(FabricConfig(num_modules=2))
    nic = init_nic_bank(4)
    mem2, nic2, ld, pd, lm, pm = serve_dual_two_leg(
        mem, nic, 1, 3, partition=True, now=0.0,
        line_ready=0.0, line_bytes=64.0, line_gate=True,
        page_ready=0.0, page_bytes=4096.0, page_gate=True, active=False)
    assert float(ld) == float(lm) and float(pd) == float(pm)
    for leaf, ref in zip(jax.tree.leaves(nic2), jax.tree.leaves(nic)):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
    assert float(unit_bytes(nic2).sum()) == 0.0


def test_two_leg_active_prices_nic_ingress():
    """A busy NIC delays the combined arrival past the module completion,
    and the gated bytes land on BOTH ledgers."""
    mem = fabric.init_fabric(FabricConfig(num_modules=2))
    nic = init_nic_bank(4)
    # pre-load unit 3's NIC page channel far into the future
    nic = nic._replace(page_busy=nic.page_busy.at[3].set(1e6))
    mem2, nic2, ld, pd, lm, pm = serve_dual_two_leg(
        mem, nic, 0, 3, partition=True, now=0.0,
        line_ready=0.0, line_bytes=64.0, line_gate=True,
        page_ready=0.0, page_bytes=4096.0, page_gate=True, active=True)
    assert float(pd) > float(pm)           # NIC ingress is the later leg
    assert float(pd) >= 1e6
    np.testing.assert_allclose(float(mem2.page_bytes[0]), 4096.0)
    np.testing.assert_allclose(float(nic2.page_bytes[3]), 4096.0)
    np.testing.assert_allclose(float(nic2.line_bytes[3]), 64.0)
    # writeback leg mirrors the same gating
    mem3, nic3, done = serve_writeback_two_leg(
        mem2, nic2, 0, 3, 0.0, 512.0, gate=True, active=True)
    assert float(mem3.wb_bytes[0]) == 512.0
    assert float(nic3.wb_bytes[3]) == 512.0


def test_shard_unit_covers_units_and_keeps_page_affinity():
    pages = jnp.arange(4096, dtype=jnp.int32)
    cu = np.asarray(shard_unit(pages, 4))
    assert cu.min() == 0 and cu.max() == 3
    # every unit gets a fair share of the page space
    counts = np.bincount(cu, minlength=4)
    assert counts.min() > 4096 // 8
    # deterministic: a page always shards to the same unit
    np.testing.assert_array_equal(cu, np.asarray(shard_unit(pages, 4)))
    # one active unit -> everything on unit 0 (the seed path)
    assert np.asarray(shard_unit(pages, 1)).max() == 0
    # unit choice decorrelates from interleave placement (page % M):
    # each module's pages spread over all units
    for m in range(4):
        assert len(set(cu[np.arange(4096) % 4 == m])) == 4


def test_nic_link_derives_mean_bandwidth_and_schedule():
    mem_link = fabric.LinkModel(
        bw=jnp.asarray([10.0, 30.0], jnp.float32),
        sched_t=jnp.asarray([0.0, 100.0], jnp.float32),
        sched_mult=jnp.asarray([[1.0, 1.0], [0.5, 0.1]], jnp.float32),
        health=jnp.asarray([[1.0, 1.0], [1.0, 0.0]], jnp.float32))
    nl = nic_link_for(mem_link, 3)
    assert nl.bw.shape == (3,)
    np.testing.assert_allclose(np.asarray(nl.bw), 20.0)
    # ambient contention (mean mult) carries over; health stays 1 (a
    # module link failure is not a NIC failure)
    np.testing.assert_allclose(float(fabric.link_bw_at(nl, 1, 150.0)),
                               20.0 * 0.3)
    np.testing.assert_allclose(np.asarray(nl.health), 1.0)


# --------------------------------------------------- C=1 bit-identity pin
@pytest.fixture(scope="module")
def golden():
    return json.loads(GOLDEN.read_text())


def test_num_cu1_lattice_bit_identical_to_seed_golden(golden):
    """num_cu=1 (the default envelope, one active unit) reproduces the
    seed golden capture — the compute plane's NIC leg and per-unit state
    axes must not perturb the single-unit arithmetic."""
    wl = "pr"
    rec = golden["workloads"][wl]
    names = golden["schemes"]
    tr = generate_trace(WORKLOADS[wl], golden["r"], seed=rec["seed"])
    nets = [make_net(NetworkParams(bw_factor=bf, switch_latency_ns=sw))
            for sw, bf in golden["net_pairs"]]
    res = simulate_lattice([SCHEMES[s] for s in names],
                           SimConfig(num_cu=1), tr, nets,
                           rec["comp_ratio"])
    for i, s in enumerate(names):
        for j in range(len(nets)):
            for key, new in res[i][j].items():
                np.testing.assert_allclose(
                    new, rec["schemes"][s][j][key], rtol=1e-5, atol=1e-6,
                    err_msg=f"{s}/net{j}/{key}")


def test_num_cu1_nic_banks_stay_idle():
    """One active unit: the NIC channel clocks and byte ledgers never
    move — the two-leg service is gated off, not merely cheap."""
    w = WORKLOADS["pr"]
    tr = generate_trace(w, 1200, seed=3)
    fin = run_trace(SCHEMES["daemon"], SimConfig(num_cu=1), tr,
                    make_net(NetworkParams()), w.comp_ratio)
    assert float(fin.stats["net_bytes"]) > 0
    for leaf in (fin.nic.line_busy, fin.nic.page_busy, fin.nic.wb_busy,
                 fin.nic.line_bytes, fin.nic.page_bytes, fin.nic.wb_bytes):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def test_envelope_with_one_active_unit_matches_num_cu1():
    """A wide (C=4) envelope with active_cus=[1] produces the same
    metrics as the num_cu=1 config — the envelope only sizes arrays."""
    w = WORKLOADS["bc"]
    tr = generate_trace(w, 1500, seed=9)
    nets = [make_net(NetworkParams())]
    schemes = [SCHEMES["remote"], SCHEMES["daemon"]]
    ref = simulate_lattice(schemes, SimConfig(num_cu=1), tr, nets,
                           w.comp_ratio)
    wide = simulate_lattice(schemes, SimConfig(num_cu=4), tr, nets,
                            w.comp_ratio, active_cus=[1])
    for i in range(len(schemes)):
        for key, v in ref[i][0].items():
            np.testing.assert_allclose(wide[i][0][0][key], v, rtol=1e-6,
                                       err_msg=key)


# ------------------------------------------------------- single compile
def test_schemes_by_cu_lattice_single_compile():
    """schemes x nets x C adds exactly ONE jit trace: the active unit
    count is data on the lattice's compute axis (like the link-profile
    knots), not shape."""
    w = WORKLOADS["bc"]
    tr = generate_trace(w, 700, seed=5)
    cfg = SimConfig(num_cu=8, num_mc=2)
    nets = [make_net(NetworkParams(), num_mc=2),
            make_net(NetworkParams(bw_factor=8.0), num_mc=2)]
    schemes = [SCHEMES[s] for s in ("remote", "pq", "daemon")]
    before = lattice_cache_size()
    simulate_lattice(schemes, cfg, tr, nets, w.comp_ratio,
                     active_cus=(1, 2, 4, 8))
    assert lattice_cache_size() - before == 1
    # different active mix, same sweep length: still no recompile
    simulate_lattice(schemes, cfg, tr, nets, w.comp_ratio,
                     active_cus=(1, 3, 5, 7))
    assert lattice_cache_size() - before == 1


def test_active_cus_validated_against_envelope():
    w = WORKLOADS["bc"]
    tr = generate_trace(w, 200, seed=5)
    with pytest.raises(ValueError):
        simulate_lattice([SCHEMES["remote"]], SimConfig(num_cu=2), tr,
                         [make_net(NetworkParams())], w.comp_ratio,
                         active_cus=[4])


# --------------------------------------- two-endpoint byte conservation
def test_desim_two_endpoint_byte_conservation():
    """C=4 active units x M=4 modules: per-unit NIC ledgers sum ==
    per-module ledgers sum == the stats ledger's net_bytes."""
    w = WORKLOADS["pr"]
    tr = generate_trace(w, 1500, seed=7)
    net = make_net(NetworkParams(), num_mc=4)
    fin = run_trace(SCHEMES["daemon"], SimConfig(num_cu=4, num_mc=4), tr,
                    net, w.comp_ratio)
    total = float(fin.stats["net_bytes"])
    assert total > 0
    np.testing.assert_allclose(float(fabric.total_bytes(fin.net)), total,
                               rtol=1e-5)
    np.testing.assert_allclose(float(unit_bytes(fin.nic).sum()), total,
                               rtol=1e-5)
    # real spread: several units and several modules carried bytes
    assert int((np.asarray(unit_bytes(fin.nic)) > 0).sum()) > 1


def test_desim_units_contend_on_shared_modules():
    """Sharding one trace across more active units overlaps their
    compute gaps, so the run completes sooner — but the shared module
    channel serializes the union of their traffic, so the speedup stays
    well short of ideal. Two-endpoint conservation holds at every C."""
    w = WORKLOADS["pr"]
    tr = generate_trace(w, 1500, seed=7)
    net = make_net(NetworkParams())
    cfg = SimConfig(num_cu=4)
    f1 = run_trace(SCHEMES["daemon"], cfg, tr, net, w.comp_ratio,
                   active_cu=1)
    f4 = run_trace(SCHEMES["daemon"], cfg, tr, net, w.comp_ratio,
                   active_cu=4)
    for fin in (f1, f4):
        np.testing.assert_allclose(float(fabric.total_bytes(fin.net)),
                                   float(fin.stats["net_bytes"]),
                                   rtol=1e-5)
    t1 = max(float(jnp.max(f1.ring)), float(jnp.max(f1.t)))
    t4 = max(float(jnp.max(f4.ring)), float(jnp.max(f4.t)))
    assert t4 < t1                    # 4 units' issue streams overlap...
    assert t4 > t1 / 4.0              # ...but the shared pool serializes
    # at C=4 the NIC conservation side also engages
    np.testing.assert_allclose(float(unit_bytes(f4.nic).sum()),
                               float(f4.stats["net_bytes"]), rtol=1e-5)
    assert float(unit_bytes(f1.nic).sum()) == 0.0


def test_store_replicated_two_endpoint_conservation():
    """Replicated store (C=3, B=2, M=2) with writes: per-unit NIC bytes
    sum == per-module bytes sum == wire_bytes (incl. writebacks)."""
    cfg = KVStoreConfig(num_local_pages=4, page_tokens=8, kv_heads=2,
                        head_dim=16, page_budget_per_step=2,
                        fabric=FabricConfig(num_modules=2))
    c, b = 3, 2
    state = init_kv_store_replicated(cfg, c, b)
    remote = jnp.zeros((48, 8, 2, 16), jnp.float32)
    rng = np.random.default_rng(11)
    fetch = jax.jit(lambda s, need, off, wr: step_fetch_replicated(
        s, cfg, remote, remote, need, off, wr))
    for _ in range(20):
        need = jnp.asarray(rng.integers(0, 48, size=(c, b, 3)), jnp.int32)
        offs = jnp.asarray(rng.integers(0, 64, size=(c, b, 3)), jnp.int32)
        wr = jnp.asarray(rng.random((c, b, 3)) < 0.5)
        state, *_ = fetch(state, need, offs, wr)
    led = ledger(state)
    assert led["wire_bytes"] > 0
    np.testing.assert_allclose(sum(led["module_bytes"]),
                               led["wire_bytes"], rtol=1e-5)
    np.testing.assert_allclose(sum(led["unit_bytes"]),
                               led["wire_bytes"], rtol=1e-5)
    assert fetch._cache_size() == 1       # replicated single-compile


def test_store_replicated_c1_is_batched():
    """One replica: NIC leg gated off — channel clocks and every stat
    match `step_fetch_batch` exactly, and the NIC bank stays idle."""
    cfg = KVStoreConfig(num_local_pages=4, page_tokens=8, kv_heads=2,
                        head_dim=16, page_budget_per_step=1,
                        fabric=FabricConfig(num_modules=2))
    remote = jnp.zeros((16, 8, 2, 16), jnp.float32)
    rng = np.random.default_rng(2)
    st_r = init_kv_store_replicated(cfg, 1, 2)
    st_b = init_kv_store_batch(cfg, 2)
    for _ in range(12):
        need = jnp.asarray(rng.integers(0, 16, size=(2, 3)), jnp.int32)
        offs = jnp.asarray(rng.integers(0, 64, size=(2, 3)), jnp.int32)
        wr = jnp.asarray(rng.random((2, 3)) < 0.5)
        st_r, *_ = step_fetch_replicated(st_r, cfg, remote, remote,
                                         need[None], offs[None], wr[None])
        st_b, *_ = step_fetch_batch(st_b, cfg, remote, remote, need,
                                    offs, wr)
    np.testing.assert_allclose(np.asarray(st_r.fab.page_busy),
                               np.asarray(st_b.fab.page_busy))
    np.testing.assert_allclose(np.asarray(st_r.fab.line_busy),
                               np.asarray(st_b.fab.line_busy))
    for k, v in ledger(st_b).items():
        if k != "module_bytes":
            assert ledger(st_r)[k] == v, k
    assert float(unit_bytes(st_r.nic).sum()) == 0.0


def test_store_replicated_nic_separates_replica_ingress():
    """All replicas hammer ONE module: the shared module channel sees
    every replica's pages back-to-back, while each replica's NIC only
    carries its own — so the NIC horizon stays well short of the shared
    module horizon (the two-leg model actually separates endpoints)."""
    cfg = KVStoreConfig(num_local_pages=4, page_tokens=8, kv_heads=2,
                        head_dim=16, page_budget_per_step=1,
                        fabric=FabricConfig(num_modules=1))
    c, b = 4, 1
    state = init_kv_store_replicated(cfg, c, b)
    remote = jnp.zeros((32, 8, 2, 16), jnp.float32)
    # replica i requests distinct pages {8i..8i+2}: same module (M=1)
    need = jnp.asarray([[[i * 8 + j for j in range(3)]]
                        for i in range(c)], jnp.int32)
    state, *_ = step_fetch_replicated(state, cfg, remote, remote, need)
    mod_busy = float(state.fab.page_busy.max())
    nic_busy = float(state.nic.page_busy.max())
    assert nic_busy > 0.0                 # ingress is priced...
    assert nic_busy < mod_busy            # ...but the pool is the choke
    # and the per-replica ledgers each carry exactly their own pages
    per_unit = np.asarray(unit_bytes(state.nic))
    assert (per_unit > 0).all()
    np.testing.assert_allclose(per_unit, per_unit[0])


# ------------------------------------------------- store writeback path
def test_store_writeback_path_accounts_dirty_evictions():
    """Locally-written pages evicted from the pool pay writeback wire
    bytes through the fabric's writeback channel; read-only traffic
    never does. Conservation (fabric == stats) holds either way."""
    cfg = KVStoreConfig(num_local_pages=2, page_tokens=8, kv_heads=2,
                        head_dim=16, page_budget_per_step=8,
                        fabric=FabricConfig(num_modules=2))
    remote = jnp.zeros((32, 8, 2, 16), jnp.float32)

    def run(write):
        state = init_kv_store_batch(cfg, 1)
        for t in range(48):
            # dwell on a page pair long enough to land + hit (the hits
            # WRITE the resident copies), then move on — the advancing
            # window evicts the written pages from the 2-slot pool
            q = (t // 6 * 2) % 24
            need = jnp.asarray([[q, q + 1]], jnp.int32)
            wr = jnp.full((1, 2), write)
            state, *_ = step_fetch_batch(state, cfg, remote, remote,
                                         need, None, wr)
        return ledger(state), state

    led_ro, _ = run(False)
    assert led_ro["writeback_bytes"] == 0.0
    led_rw, st_rw = run(True)
    assert led_rw["writeback_bytes"] > 0.0
    assert led_rw["dirty_evicts"] > 0.0
    assert float(st_rw.fab.wb_bytes.sum()) == led_rw["writeback_bytes"]
    np.testing.assert_allclose(sum(led_rw["module_bytes"]),
                               led_rw["wire_bytes"], rtol=1e-5)


def test_store_writeback_throttles_through_dirty_unit():
    """A dirty eviction whose page is back inflight rides the §4.3 dirty
    unit (buffered, no wire) until the threshold; unbuffered evictions
    pay wire. The single-sequence stepper exercises the same path."""
    cfg = KVStoreConfig(num_local_pages=1, page_tokens=8, kv_heads=2,
                        head_dim=16, page_budget_per_step=1,
                        fabric=FabricConfig(num_modules=1))
    remote = jnp.zeros((8, 8, 2, 16), jnp.float32)
    from repro.core.daemon_store import init_kv_store
    state = init_kv_store(cfg)
    wr = jnp.asarray([True])
    # alternate two pages through a 1-slot pool with writes: every
    # landing evicts the other (written) page
    for t in range(30):
        need = jnp.asarray([t % 2], jnp.int32)
        state, *_ = step_fetch(state, cfg, remote, remote, need, None, wr)
    led = ledger(state)
    assert led["dirty_evicts"] > 0.0
    assert led["writeback_bytes"] > 0.0
    np.testing.assert_allclose(sum(led["module_bytes"]),
                               led["wire_bytes"], rtol=1e-5)


# ------------------------------------- writeback loop over dirty evictions
def _wb_cfg():
    from repro.core.params import DaemonParams
    return KVStoreConfig(num_local_pages=48, page_tokens=8, kv_heads=2,
                         head_dim=16, page_budget_per_step=4,
                         daemon=DaemonParams(inflight_page_buf=16,
                                             dirty_flush_threshold=2),
                         fabric=FabricConfig(num_modules=4))


_HOLE = -1
_WB_CASES = {
    "all_empty": [_HOLE] * 16,
    "holes": [3, _HOLE, _HOLE, 7, _HOLE, 12, 13, _HOLE, _HOLE, _HOLE,
              21, _HOLE, _HOLE, _HOLE, 9, _HOLE],
    "all_valid": list(range(20, 36)),
    # page 5 is inflight: its dirty eviction is buffered, not written
    "inflight_buffered": [_HOLE, 5, _HOLE, 11, _HOLE, _HOLE, _HOLE, _HOLE,
                          _HOLE, _HOLE, _HOLE, _HOLE, _HOLE, _HOLE, 6,
                          _HOLE],
    # page 5 offered past dirty_flush_threshold (2): throttled, then on
    "past_threshold": [5, _HOLE, 5, 5, _HOLE, 5, 17, _HOLE, 5, _HOLE,
                       _HOLE, _HOLE, _HOLE, _HOLE, _HOLE, 5],
}


@pytest.mark.parametrize("two_leg", [False, True],
                         ids=["single_endpoint", "two_leg"])
@pytest.mark.parametrize("case", sorted(_WB_CASES))
def test_writeback_loop_matches_full_scan(case, two_leg):
    """The writeback loop visits only the valid evictions, yet leaves the
    engine, the fabric, the NIC bank and the writeback count bitwise as a
    scan over all 16 (-1 padded) slots does; it makes one offer per valid
    entry."""
    from repro.core.daemon_store import (_offer_evictions,
                                         _writeback_offer)
    from repro.core.engine import init_engine_state, schedule_page
    cfg = _wb_cfg()
    evicted = jnp.asarray(_WB_CASES[case], jnp.int32)
    state = init_kv_store_replicated(cfg, 2, 1)
    eng = schedule_page(init_engine_state(cfg.daemon), 5, 2.0, 9.0)
    # a writeback channel already busy past the clock on one module
    fab = state.fab._replace(wb_busy=state.fab.wb_busy.at[1].set(4.5))
    nic = state.nic if two_leg else None
    clock = jnp.float32(3.0)
    wb_one = _writeback_offer(cfg, clock, two_leg=two_leg, cu=1,
                              active=True)

    @jax.jit
    def full_scan(carry, ev):
        carry, wrote_back = jax.lax.scan(wb_one, carry, ev)
        return carry, jnp.sum(wrote_back)

    want, want_wb = full_scan((eng, fab, nic), evicted)
    got, got_wb, offers = jax.jit(
        lambda c, ev: _offer_evictions(wb_one, c, ev))((eng, fab, nic),
                                                       evicted)
    assert got_wb.dtype == want_wb.dtype
    assert int(got_wb) == int(want_wb)
    assert int(offers) == sum(p >= 0 for p in _WB_CASES[case])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if case == "all_valid":
        assert int(got_wb) == 16
    if case == "inflight_buffered":
        assert int(got_wb) == 2           # 11 and 6; 5 is buffered
    if case == "past_threshold":
        assert int(got_wb) > 1            # page 5 throttled, 17 written


def test_step_without_landing_leaves_writeback_state():
    """A batched step in which nothing lands offers no eviction: the
    writeback channels, their ledgers and every engine leaf stay bitwise
    as the step found them, and `writeback_offers` adds 0."""
    cfg = _wb_cfg()
    remote = jnp.zeros((64, 8, 2, 16), jnp.float32)
    step = jax.jit(lambda s, p, w: step_fetch_batch(s, cfg, remote,
                                                    remote, p, None, w))
    state = init_kv_store_batch(cfg, 3)
    need = jnp.asarray([[0, 1], [8, 9], [16, 17]], jnp.int32)
    writes = jnp.ones((3, 2), bool)
    for _ in range(12):                    # land, then hit (and dirty)
        state, _, _, hit = step(state, need, writes)
    assert bool(hit.all())
    assert int((state.seqs.eng.page_key >= 0).sum()) == 0   # none inflight
    before = state
    state, _, _, hit = step(state, need, writes)
    assert bool(hit.all())
    np.testing.assert_array_equal(np.asarray(state.fab.wb_busy),
                                  np.asarray(before.fab.wb_busy))
    np.testing.assert_array_equal(np.asarray(state.fab.wb_bytes),
                                  np.asarray(before.fab.wb_bytes))
    for a, b in zip(jax.tree.leaves(state.seqs.eng),
                    jax.tree.leaves(before.seqs.eng)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(state.seqs.stats["writeback_offers"]),
        np.asarray(before.seqs.stats["writeback_offers"]))
    assert ledger(state)["writeback_offers"] == 0.0
