"""The trace reducers give known numbers on a small recorded trace."""
import json

import pytest

import harness
import roofline
import trace_reduce
from benchtest_support import BENCH
from peaks import peaks

FIXTURE = json.loads((BENCH / "tests" / "fixture_trace.json").read_text())


def _metric(name, ctx):
    mod = harness.load_module(BENCH / "metrics" / f"{name}.py", f"f_{name}")
    return mod.read(FIXTURE, ctx)


def _ctx(**kw):
    busy = trace_reduce.reduce_busy(FIXTURE)
    return dict({"busy": busy, "units": 2, "device_kind": "TPU v5 lite",
                 "ledger_delta": {"evictions": 3, "local_hits": 5},
                 "stores": 2, "slots": 48, "row_bytes": 65536}, **kw)


def test_busy_is_the_union_inside_the_window():
    b = trace_reduce.reduce_busy(FIXTURE)
    # window 1000..11000 ns; ops cover 1000-3000, 2500-4000 (overlap),
    # 6000-7000 and 10500-12000 (clipped at 11000)
    assert b["window_s"] == pytest.approx(10000e-9)
    assert b["busy_s"] == pytest.approx((3000 + 1000 + 500) * 1e-9)


def test_kernel_time_by_stable_name():
    assert trace_reduce.kernel_s(FIXTURE, "residency_fused") == \
        pytest.approx(1500e-9)
    assert trace_reduce.kernel_s(FIXTURE, "paged_gather") == \
        pytest.approx(1000e-9)
    assert trace_reduce.kernel_s(FIXTURE, "no_such_kernel") is None


def test_layer_readers():
    ctx = _ctx()
    assert _metric("store.step_device_ms", ctx) == pytest.approx(4500e-9
                                                                / 2 * 1e3)
    assert _metric("store.residency_kernel_ms", ctx) == pytest.approx(
        1500e-9 / 2 * 1e3)
    assert _metric("store.remote_fetch_ms", ctx) == pytest.approx(
        1000e-9 / 2 * 1e3)
    assert _metric("device_idle_pct.store", ctx) == pytest.approx(55.0)
    assert _metric("sim.step_us", ctx) is None      # no scan in the trace
    nbytes = roofline.residency_bytes(ctx)
    assert nbytes == 2 * 2 * 2 * 48 * 20 + 4 * 65536 * (3 + 5)
    assert _metric("residency_fused_roofline", ctx) == pytest.approx(
        100 * nbytes / 819e9 / 1500e-9)


def test_reader_finds_nothing_returns_nothing():
    empty = dict(FIXTURE, device={"/device:TPU:0": []})
    ctx = _ctx(busy=trace_reduce.reduce_busy(empty))
    mod = harness.load_module(BENCH / "metrics"
                              / "residency_fused_roofline.py", "f_empty")
    assert mod.read(empty, ctx) is None
    mod = harness.load_module(BENCH / "metrics" / "device_idle_pct.store.py",
                              "f_empty2")
    assert mod.read(empty, ctx) is None


def test_breakdown_names_gaps_by_host_span():
    bd = trace_reduce.breakdown(FIXTURE)
    assert bd["device_ops"][0][0] == "fusion.7 fusion"
    gaps = dict((n, s) for n, s in bd["idle_gaps"])
    assert gaps["bench.step.dispatch"] == pytest.approx(2000e-9)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_peaks_table():
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError):
        peaks("TPU v9 imaginary")
    assert roofline.roofline_pct(819e9, 1.0, "TPU v5 lite") == \
        pytest.approx(100.0)


def test_scan_steps_and_sim_step():
    ops = []
    for i in range(5):                       # 5 iterations of a 2-op body
        ops += [["fusion.a", 100.0 * i, 40.0],
                ["fusion.b", 100.0 * i + 40, 40.0]]
    ops.append(["while.1", 0.0, 480.0])
    tr = {"window": [0.0, 500.0], "device": {"/device:TPU:0": ops},
          "host": []}
    assert trace_reduce.scan_steps(tr) == 5
    busy = trace_reduce.reduce_busy(tr)
    assert busy["busy_s"] == pytest.approx(480e-9)
    mod = harness.load_module(BENCH / "metrics" / "sim.step_us.py", "f_sim")
    assert mod.read(tr, {"busy": busy}) == pytest.approx(480e-9 / 5 * 1e6)


def _boundary_trace(first_end, second_start, w1=1000.0):
    ops = [["fusion.a", float(t), 10.0] for t in range(0, int(first_end), 10)]
    ops += [["fusion.a", float(t), 10.0]
            for t in range(int(second_start), int(w1), 10)]
    ops.append(["fusion.a", 300.0 - 5.0, 5.0])      # lies inside busy time
    return {"window": [0.0, w1], "device": {"/device:TPU:0": ops},
            "host": []}


def test_boundary_idle_is_the_gap_over_the_period():
    tr = _boundary_trace(400.0, 450.0)
    mod = harness.load_module(BENCH / "metrics" / "device_idle_pct.sim.py",
                              "f_idle_sim")
    # a 50 ns gap in a 5000 ns period, no other idle time
    assert mod.read(tr, {"period_s": 5000e-9}) == pytest.approx(1.0)
    # idle inside the lattice counts at the rate the rest of the window has
    ops = [ev for ev in tr["device"]["/device:TPU:0"] if ev[1] != 600.0]
    tr2 = dict(tr, device={"/device:TPU:0": ops})   # 10 ns more idle
    rate = 10.0 / 950.0
    want = 100.0 * (50.0 + rate * (5000.0 - 50.0)) / 5000.0
    assert mod.read(tr2, {"period_s": 5000e-9}) == pytest.approx(want)


def test_boundary_idle_needs_the_gap_inside_the_window():
    edge = _boundary_trace(0.0, 300.0)              # idle from the start
    assert trace_reduce.boundary_idle_pct(edge, 5000e-9) is None
    assert trace_reduce.boundary_idle_pct(_boundary_trace(400.0, 450.0),
                                          None) is None
