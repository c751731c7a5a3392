"""Phase scopes: a small scoped program compiled on the CPU, and a trace
made from its instruction names, give the scope times the readers
report."""
import jax
import jax.numpy as jnp
import pytest

import harness
import scopes
from benchtest_support import BENCH


def _compiled(fold="store.schedule_fold", inner="store.request_scan",
              serve="store.serve"):
    """A program shaped like the store step: a scan under `fold` whose
    body is under `inner`, then a `serve` phase, then an op under no
    scope."""
    def body(c, x):
        with jax.named_scope(inner):
            c = jnp.sin(c) * x
        return c, c.sum()

    def f(a, xs):
        with jax.named_scope(fold):
            c, ys = jax.lax.scan(body, a, xs)
        with jax.named_scope(serve):
            c = jnp.cos(c) + ys.sum()
        return c * 3.0

    return jax.jit(f).lower(jnp.ones(8), jnp.ones((4, 8))).compile()


def _program(**names):
    """The optimized HLO text of `_compiled(**names)`."""
    return _compiled(**names).as_text()


def _op(table, want, avoid=()):
    """A short name of the table whose op_name holds every scope in
    `want` and none in `avoid`."""
    for key, op in table.items():
        comps = scopes.components(op or "")
        if all(w in comps for w in want) and not comps & set(avoid):
            return key
    raise AssertionError(f"no instruction under {want}")


def _event(key, start, dur):
    name, opcode = key.split(" ")
    return [f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", start, dur]


@pytest.fixture(scope="module")
def case():
    table = scopes.build_table([_program()])
    loop = _op(table, ["store.schedule_fold"], ["store.request_scan"])
    inner = _op(table, ["store.request_scan"])
    serve = _op(table, ["store.serve"])
    plain = next(k for k, op in table.items()
                 if op is not None and not any(
                     c.startswith("store.") for c in scopes.components(op)))
    tr = {"window": [0.0, 200.0], "host": [],
          "device": {"/device:TPU:0": [
              _event(loop, 0.0, 100.0),          # the fold's loop
              _event(inner, 10.0, 20.0),         # its body, twice
              _event(inner, 50.0, 20.0),
              _event(serve, 120.0, 20.0),
              _event(plain, 150.0, 10.0),
              _event(serve, 190.0, 20.0)]}}      # cut by the window
    return table, tr


def test_scope_time_counts_nested_ops_once(case):
    table, tr = case
    # the loop covers its body: the fold is the loop's 100 ns, once
    assert scopes.scope_s(tr, "store.schedule_fold", table) == \
        pytest.approx(100e-9)
    assert scopes.scope_s(tr, "store.request_scan", table) == \
        pytest.approx(40e-9)
    assert scopes.scope_s(tr, "store.serve", table) == pytest.approx(30e-9)
    assert scopes.scope_s(tr, "store.transact", table) is None


def test_unscoped_share(case):
    table, tr = case
    busy = 100.0 + 20.0 + 10.0 + 10.0
    assert scopes.unscoped_pct(tr, "store.", table) == pytest.approx(
        100.0 * 10.0 / busy)
    assert scopes.unscoped_pct(tr, "desim.", table) is None


def test_readers_through_the_harness(case, monkeypatch):
    table, tr = case
    monkeypatch.setattr(scopes, "live_table", lambda: table)

    def read(name, ctx):
        mod = harness.load_module(BENCH / "metrics" / f"{name}.py",
                                  f"s_{name}")
        return mod.read(tr, ctx)

    ctx = {"units": 2}
    assert read("store.schedule_fold_ms", ctx) == pytest.approx(
        100e-9 / 2 * 1e3)
    assert read("store.request_scan_ms", ctx) == pytest.approx(
        40e-9 / 2 * 1e3)
    assert read("store.serve_ms", ctx) == pytest.approx(30e-9 / 2 * 1e3)
    assert read("store.unscoped_pct", ctx) == pytest.approx(
        100.0 * 10.0 / 140.0)
    # nothing under the scope, or no steps: no number
    assert read("store.transact_ms", ctx) is None
    assert read("store.writeback_scan_ms", ctx) is None
    assert read("store.serve_ms", {"units": 0}) is None
    for phase in ("unit_state", "issue", "lookup", "engine", "fabric",
                  "residency", "stats"):
        assert read(f"sim.{phase}_us", ctx) is None
    assert read("sim.unscoped_pct", ctx) is None


def test_sim_reader_divides_by_scan_steps(monkeypatch):
    table = scopes.build_table([_program(
        fold="desim.fabric", inner="desim.engine", serve="desim.stats")])
    key = _op(table, ["desim.engine"])
    ops = [_event(key, 100.0 * i, 40.0) for i in range(5)]
    tr = {"window": [0.0, 500.0], "host": [],
          "device": {"/device:TPU:0": ops}}
    monkeypatch.setattr(scopes, "live_table", lambda: table)
    mod = harness.load_module(BENCH / "metrics" / "sim.engine_us.py",
                              "s_sim_engine")
    assert mod.read(tr, {}) == pytest.approx(200e-9 / 5 * 1e6)


def test_name_two_programs_define_apart_is_unattributed(case):
    table, tr = case
    other = _program(serve="store.transact")
    both = scopes.build_table([_program(), other])
    serve = _op(table, ["store.serve"])
    # the same instructions, the serve phase named otherwise: ambiguous
    assert both[serve] is None
    assert scopes.scope_s(tr, "store.serve", both) is None
    assert scopes.scope_s(tr, "store.transact", both) is None
    # what both programs name alike keeps its scope
    assert scopes.scope_s(tr, "store.schedule_fold", both) == \
        pytest.approx(100e-9)


def test_components_unwrap_transforms():
    comps = scopes.components("jit(f)/vmap(store.serve)/while/body/add")
    assert {"store.serve", "while", "f", "add"} <= comps
    assert "store.serve" in scopes.components(
        "jit(step)/vmap(vmap(store.serve))/mul")


def test_live_table_reads_the_live_programs():
    compiled = _compiled()                      # held: it stays live
    live = scopes.live_table()
    mine = scopes.build_table([compiled.as_text()])
    assert mine and set(mine) <= set(live)
    assert scopes.live_table() is live          # nothing compiled since
