"""The latent store's cell (`store-dsv3-fullctx-b8`, entry `store_latent`)
at a size a test run holds, on the CPU: a whole run reads `correct` true,
its pages land, evict and write back, and it reads false with the control
in the program's place or with the timed path broken underneath (half
the batch left out, one served row altered)."""
import time

import jax
import pytest

import control
import harness

CELL = "store-dsv3-fullctx-b8"
# the same configuration and mix: fewer tenants and layers, a 4096-token
# context read whole each step (64 pages of 64 tokens x 576 values), a
# 12-page pool (3 sets x 4 ways, the cell's 18.75%), shorter streams
SCALE = {"mix": {"tenants": 2, "requests_per_step": 64, "start_min": 4032,
                 "stream_steps": 12, "warm_steps": 2, "trace_units": 2},
         "model": {"num_hidden_layers": 2},
         "store": {"context_tokens": 4096, "local_pages_per_tenant": 12,
                   "pool_ways": 4}}
SEED = 2**31 + 11


@pytest.fixture(autouse=True)
def _fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(trace=False):
    return harness.run(CELL, SEED, 0.3, trace, t_start=time.perf_counter(),
                       require_tpu=False, scale=SCALE, log=lambda msg: None)


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_latent_run_is_correct(trace):
    out = _run(trace)
    assert out["correct"], out["compared"]
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    if trace:
        assert out["device"]["window_s"] > 0 and "breakdown" in out
    else:
        assert out["metrics"]["store_tenant_steps_per_s"]["value"] > 0


def test_latent_cell_moves_pages():
    """The full-context read misses most requests, and its pages land,
    evict, and (the written newest page) go back dirty: the miss path is
    the work. The two counters the roofline shares read are kept."""
    r = harness.resolve(CELL)
    cell = r["entry"].Cell(r["cfg"], r["mix"], SEED,
                           harness.devices(1, False), SCALE)
    cell.setup()
    for _ in range(6):
        cell.unit()
    ctx = cell.layer_context(6)
    d = ctx["ledger_delta"]
    assert d["landings"] > 0 and d["remote_rows"] > 0
    assert d["local_hits"] < 0.2 * d["requests"]
    assert ctx["row_bytes"] == 64 * 576 * 2
    from repro.core import daemon_store
    led = daemon_store.ledger(cell.state)
    for k in ("evictions", "dirty_evicts", "writeback_offers"):
        assert led[k] > 0, k


def test_latent_control_reads_incorrect():
    _, ctl, _ = control.readings(CELL, SEED + 10, 0.2, require_tpu=False,
                                 scale=SCALE)
    limits = harness.resolve(CELL)["entry"].LIMITS
    assert set(ctl) == set(limits)
    assert ctl["served_rows_wrong"] > limits["served_rows_wrong"]
    assert ctl["pool_rows_wrong"] > limits["pool_rows_wrong"]


def _patch_store(monkeypatch, fault):
    from repro.core import daemon_store
    real = daemon_store.step_fetch_batch

    def broken(state, cfg, tier, none, need, off, wr):
        new, k, v, hit = real(state, cfg, tier, none, need, off, wr)
        if fault == "half_batch":
            half = need.shape[0] // 2
            keep = lambda o, n: n.at[half:].set(o[half:])
            seqs = jax.tree.map(keep, state.seqs, new.seqs)
            return (new._replace(seqs=seqs), k.at[half:].set(0), v,
                    hit.at[half:].set(False))
        if fault == "altered":
            return new, k.at[0, 0, 0, 0].add(1), v, hit
        raise ValueError(fault)
    monkeypatch.setattr(daemon_store, "step_fetch_batch", broken)


@pytest.mark.parametrize("fault", ["half_batch", "altered"])
def test_latent_fault_reads_incorrect(monkeypatch, fault):
    _patch_store(monkeypatch, fault)
    out = _run()
    assert not out["correct"], out["compared"]
    if fault == "altered":
        assert out["compared"]["served_rows_wrong"]["value"] > 0
