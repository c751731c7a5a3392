"""A whole run on the CPU (only the look for a chip skipped) reads
`correct` true on the program as it is, and false with the timed path
broken underneath: a step that returns its state unchanged, half of the
batch left out, an answer altered where it is produced, and the control
(the reference one precision down) in the program's place."""
import jax
import jax.numpy as jnp
import pytest

import control
import harness

STORE = "store-olmoe-window-b64"


@pytest.fixture(autouse=True)
def _fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", ["sim-pr-fig8", STORE])
def test_sound_run_is_correct(run_cell, workload):
    out = run_cell(workload)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("workload", ["sim-pr-fig8", STORE])
def test_traced_run_is_correct(run_cell, workload):
    out = run_cell(workload, trace=True)
    assert out["correct"], out["compared"]
    assert out["device"]["window_s"] > 0 and "breakdown" in out


# ------------------------------------------------------------- the store
def _patch_store(monkeypatch, fault):
    from repro.core import daemon_store
    real = daemon_store.step_fetch_batch

    def broken(state, cfg, rk, rv, need, off, wr):
        new, k, v, hit = real(state, cfg, rk, rv, need, off, wr)
        if fault == "unchanged":
            return state, k, v, hit
        if fault == "half_batch":
            half = need.shape[0] // 2
            keep = lambda o, n: n.at[half:].set(o[half:])
            seqs = jax.tree.map(keep, state.seqs, new.seqs)
            return (new._replace(seqs=seqs), k.at[half:].set(0),
                    v.at[half:].set(0), hit.at[half:].set(False))
        if fault == "altered":
            return new, k.at[0, 0, 0, 0, 0].add(1), v, hit
        raise ValueError(fault)
    monkeypatch.setattr(daemon_store, "step_fetch_batch", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_store_fault_reads_incorrect(run_cell, monkeypatch, fault):
    _patch_store(monkeypatch, fault)
    out = run_cell(STORE)
    assert not out["correct"], out["compared"]


# --------------------------------------------------------- the simulator
def _patch_sim(monkeypatch, fault):
    from repro.sim import desim
    if fault == "unchanged":
        real_make = desim.make_step

        def make(*a, **k):
            step = real_make(*a, **k)
            return lambda st, inp: (st, step(st, inp)[1])
        monkeypatch.setattr(desim, "make_step", make)
        return
    real = desim._lattice_jit

    def broken(cfg, n_pages, telcfg, tflags, *rest):
        if fault == "half_batch":
            s = tflags.local_only.shape[0]
            half = jax.tree.map(lambda x: x[: s // 2], tflags)
            rest = (rest[0], rest[1], rest[2], rest[3][: s // 2]) + rest[4:]
            res = real.__wrapped__(cfg, n_pages, telcfg, half, *rest)
            return jax.tree.map(lambda x: jnp.concatenate(
                [x, x[: s - s // 2]]), res)
        res = real.__wrapped__(cfg, n_pages, telcfg, tflags, *rest)
        return dict(res, total_time_ns=res["total_time_ns"] * 1.001)
    jitted = jax.jit(broken, static_argnums=(0, 1, 2))
    monkeypatch.setattr(desim, "_lattice_jit", jitted)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_sim_fault_reads_incorrect(run_cell, monkeypatch, fault):
    _patch_sim(monkeypatch, fault)
    out = run_cell("sim-pr-fig8")
    assert not out["correct"], out["compared"]


# ------------------------------------------------------------ the control
@pytest.mark.parametrize("workload", ["sim-pr-fig8", STORE])
def test_control_reads_incorrect(workload):
    from benchtest_support import SCALES
    _, ctl, _ = control.readings(workload, 2**31 + 21, 0.2,
                                 require_tpu=False, scale=SCALES[workload])
    limits = harness.resolve(workload)["entry"].LIMITS
    assert set(ctl) == set(limits)
    assert any(ctl[k] > limits[k] for k in ctl), ctl
