"""Fused residency transaction: Pallas kernel (interpret) vs jnp oracle,
fused-vs-chain store bit-identity, compile counts, BENCH schema."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import residency
from repro.core.daemon_store import (KVStoreConfig, init_kv_store_batch,
                                     step_fetch_batch)
from repro.core.fabric import FabricConfig
from repro.kernels import ref as R
from repro.kernels import residency_fused as RF

POLICY_NAMES = ("lru", "fifo", "rrip", "dirty-averse")
OUT_NAMES = ("res.page", "res.age", "res.ready", "res.dirty", "res.rrpv",
             "kpool", "vpool", "evicted", "n_ev", "k_local", "v_local",
             "hit")


def _rand_case(seed, b=2, s=4, w=3, p=6, r_req=5, pr=32, row=(2, 1, 4)):
    """A random engine snapshot that respects the CAM invariants the
    engine guarantees: set placement (page % S == set), no duplicate
    resident page per set, landed (in-flight) pages distinct and not
    already resident, some resident entries still in flight (ready tag
    in the future), random dirty bits."""
    rng = np.random.default_rng(seed)
    n = s * w
    clock = 12.0
    page = np.full((b, s, w), -1, np.int64)
    for bi in range(b):
        for si in range(s):
            # candidate pages of this set, occupancy ~60%
            cand = rng.permutation(np.arange(si, pr, s))
            k = min(w, len(cand))
            occ = rng.random(k) < 0.6
            page[bi, si, :k] = np.where(occ, cand[:k], -1)
    occ = page >= 0
    age = np.where(occ, rng.uniform(0, 10, (b, s, w)), 0.0)
    ready = np.where(occ,
                     np.where(rng.random((b, s, w)) < 0.3, clock + 5.0,
                              age),
                     3.0e38)
    dirty = occ & (rng.random((b, s, w)) < 0.4)
    rrpv = np.where(occ, rng.integers(0, 4, (b, s, w)), 3.0)
    landed = rng.random((b, p)) < 0.5
    lp = np.full((b, p), -1, np.int64)
    for bi in range(b):
        seen = set(page[bi].ravel().tolist())
        for i in range(p):
            v = int(rng.integers(0, pr))
            while v in seen:
                v = (v + 1) % pr
            seen.add(v)
            lp[bi, i] = v
    lp = np.where(landed, lp, -1)
    res = residency.ResidencyState(
        page=jnp.asarray(page, jnp.int32), age=jnp.asarray(age, jnp.float32),
        ready=jnp.asarray(ready, jnp.float32), dirty=jnp.asarray(dirty),
        rrpv=jnp.asarray(rrpv, jnp.float32))
    kpool = jnp.asarray(rng.standard_normal((b, n) + row), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((b, n) + row), jnp.float32)
    rk = jnp.asarray(rng.standard_normal((pr,) + row), jnp.float32)
    rv = jnp.asarray(rng.standard_normal((pr,) + row), jnp.float32)
    needed = jnp.asarray(rng.integers(0, pr, (b, r_req)), jnp.int32)
    writes = jnp.asarray(rng.random((b, r_req)) < 0.5)
    return (res, kpool, vpool, rk, rv, jnp.asarray(landed),
            jnp.asarray(lp, jnp.int32), needed, writes,
            jnp.asarray(clock, jnp.float32))


def _assert_same(oracle, kernel):
    flat_o = list(oracle[0]) + list(oracle[1:])
    flat_k = list(kernel[0]) + list(kernel[1:])
    for nm, a, b in zip(OUT_NAMES, flat_o, flat_k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=nm)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from(POLICY_NAMES))
def test_fused_kernel_matches_oracle(seed, pol_name):
    """Pallas kernel (interpret mode, the CPU validation path) is exactly
    the jnp oracle on every output — metadata, pools, writeback list,
    local gathers — for random snapshots under every policy."""
    pol = residency.as_policy(pol_name)
    args = _rand_case(seed)
    _assert_same(R.fused_residency_step(*args, pol),
                 RF.fused_residency_step(*args, pol, interpret=True))


@pytest.mark.parametrize("pol_name", POLICY_NAMES)
def test_fused_kernel_matches_oracle_latent(pol_name):
    """The latent layout (one page tensor: a 64-token page of 576 values
    held as (288, 128) tiles, `vpool = remote_v = None`): the kernel
    moves one pool and equals the oracle on every output, and both give
    None for the v pool and the v rows."""
    pol = residency.as_policy(pol_name)
    (res, kpool, _, rk, _, landed, lp, needed, writes,
     clock) = _rand_case(3, row=(288, 128))
    args = (res, kpool.astype(jnp.bfloat16), None,
            rk.astype(jnp.bfloat16), None, landed, lp, needed, writes,
            clock)
    oracle = R.fused_residency_step(*args, pol)
    kernel = RF.fused_residency_step(*args, pol, interpret=True)
    assert oracle[2] is None and oracle[6] is None
    assert kernel[2] is None and kernel[6] is None
    _assert_same(oracle, kernel)
    assert bool(jnp.any(oracle[7])) and bool(jnp.any(landed))


def test_dma_view_moves_latent_rows_as_they_are():
    """A latent page held as (288, 128) bfloat16 tiles takes the no-copy
    branch of `_dma_view` (pools, tier and rows move without a relayout);
    a K/V row that is not whole tiles still gets the padded view."""
    pool = jnp.zeros((2, 12, 288, 128), jnp.bfloat16)
    view, undo = RF._dma_view(pool, 2)
    assert view is pool and undo(view) is view
    odd = jnp.zeros((2, 12, 4, 1, 8), jnp.bfloat16)
    assert RF._dma_view(odd, 2)[0].shape[-1] == 128


@pytest.mark.parametrize("pol_name", POLICY_NAMES)
def test_fused_kernel_same_set_overflow_drops(pol_name):
    """>W landings mapping to ONE set in a single step: ranks >= W must
    drop (stay un-landed) identically in kernel and oracle, and the
    surviving insertions fill exactly the set's W ways."""
    pol = residency.as_policy(pol_name)
    s, w, p, pr = 2, 2, 6, 32
    res = residency.init_residency(s, w)
    res = jax.tree.map(lambda x: x[None], res)          # B=1
    rng = np.random.default_rng(0)
    row = (2, 1, 4)
    kpool = jnp.zeros((1, s * w) + row, jnp.float32)
    vpool = jnp.zeros((1, s * w) + row, jnp.float32)
    rk = jnp.asarray(rng.standard_normal((pr,) + row), jnp.float32)
    landed = jnp.ones((1, p), bool)
    # all six landed pages are even -> set 0; only W=2 can land
    lp = jnp.asarray([[0, 2, 4, 6, 8, 10]], jnp.int32)
    needed = jnp.asarray([[0, 2, 4]], jnp.int32)
    writes = jnp.zeros((1, 3), bool)
    clock = jnp.asarray(1.0, jnp.float32)
    args = (res, kpool, vpool, rk, rk, landed, lp, needed, writes, clock)
    oracle = R.fused_residency_step(*args, pol)
    _assert_same(oracle, RF.fused_residency_step(*args, pol,
                                                 interpret=True))
    page = np.asarray(oracle[0].page)[0]
    assert set(page[0].tolist()) == {0, 2}   # first W by request order
    assert set(page[1].tolist()) == {-1}     # set 1 untouched
    hit = np.asarray(oracle[7])
    np.testing.assert_array_equal(hit, [[True, True, False]])


def _mini_cfg(impl, ways=0):
    return KVStoreConfig(num_local_pages=4, page_tokens=8, kv_heads=2,
                         head_dim=16, pool_ways=ways, kernel_impl=impl,
                         fabric=FabricConfig(num_modules=2))


def _drive(cfg, steps=8, batch=3, policy=None):
    rng = np.random.default_rng(7)
    remote = jnp.asarray(rng.standard_normal((32, 8, 2, 16)),
                         jnp.float32)
    state = init_kv_store_batch(cfg, batch)
    outs = []
    for _ in range(steps):
        need = jnp.asarray(rng.integers(0, 32, (batch, 2)), jnp.int32)
        wr = jnp.asarray(rng.random((batch, 2)) < 0.5)
        state, k, v, hit = step_fetch_batch(state, cfg, remote, remote,
                                            need, needed_writes=wr,
                                            policy=policy)
        outs.append((k, v, hit))
    return state, outs


@pytest.mark.parametrize("pol_name", POLICY_NAMES)
@pytest.mark.parametrize("ways", [0, 2])
def test_store_fused_matches_chain(pol_name, ways):
    """`kernel_impl="ref"` (the fused transaction) is bit-identical to
    the legacy `_land`/`_lookup` chain through a multi-step batched
    decode with writes — full state tree AND every served tensor — for
    both pool geometries (direct 1xN and set-associative)."""
    pol = residency.as_policy(pol_name)
    s_ref, o_ref = _drive(_mini_cfg("ref", ways), policy=pol)
    s_ch, o_ch = _drive(_mini_cfg("chain", ways), policy=pol)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), s_ref, s_ch)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), o_ref, o_ch)


def test_pallas_impl_raises_off_tpu():
    """`kernel_impl="pallas"` demands the compiled kernel: off a TPU it
    raises instead of falling back to interpret mode, and "auto" picks
    the oracle there."""
    from repro.kernels import ops
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        _drive(_mini_cfg("pallas", 2), steps=1)
    assert ops.resolve_impl("auto") == "ref"


def test_store_kernel_impl_single_compile():
    """The fused path keeps the store's single-compile property: one jit
    trace per (shape, kernel_impl) serves every step and every policy —
    the impl switch is static config, the policy stays traced data."""
    for impl in ("ref", "chain"):
        cfg = _mini_cfg(impl)
        remote = jnp.zeros((32, 8, 2, 16), jnp.float32)
        fetch = jax.jit(lambda s, need, pol, _cfg=cfg: step_fetch_batch(
            s, _cfg, remote, remote, need, policy=pol))
        state = init_kv_store_batch(cfg, 3)
        rng = np.random.default_rng(0)
        for pol_name in POLICY_NAMES:
            need = jnp.asarray(rng.integers(0, 32, (3, 2)), jnp.int32)
            state, _, _, _ = fetch(state, need,
                                   residency.as_policy(pol_name))
        assert fetch._cache_size() == 1, impl


STORE_SCOPES = ("store.transact", "store.serve", "store.schedule_fold",
                "store.request_scan", "store.writeback_scan")


@pytest.mark.parametrize("impl", ["ref", "chain"])
def test_store_phase_scopes_in_compiled_step(impl):
    """Every phase scope of the store step reaches the compiled
    `step_fetch_batch`'s op_name metadata, whichever transaction path
    runs: the benchmark reads each phase's device time by these names
    (bench/scopes.py)."""
    cfg = _mini_cfg(impl)
    remote = jnp.zeros((32, 8, 2, 16), jnp.float32)
    need = jnp.zeros((3, 2), jnp.int32)
    text = jax.jit(lambda s, n: step_fetch_batch(
        s, cfg, remote, remote, n, needed_writes=n > 0)).lower(
            init_kv_store_batch(cfg, 3), need).compile().as_text()
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in STORE_SCOPES:
        assert re.search(rf"[/(]{re.escape(scope)}[/)]", op_names), scope


def _tiny_producer(name, monkeypatch):
    """The BENCH_*.json producer `name` at a size a test run holds: the
    same code paths, shorter traces and fewer steps (each sweep's own
    `r`/`steps` knobs) and the fewest sweep points its headline reads."""
    from functools import partial

    from benchmarks import capacity, robustness, scaling, serving
    if name == "serve":
        monkeypatch.setattr(serving, "SWEEP", (
            ("daemon", True, 4, "interleave"),
            ("remote-style", False, 4, "interleave")))
        monkeypatch.setattr(serving, "KERNEL_BATCH", 2)
        monkeypatch.setattr(serving, "KERNEL_POOL_PAGES", 32)
        monkeypatch.setattr(serving, "KERNEL_PAGES_PER_TENANT", 64)
        monkeypatch.setattr(serving, "kernel_sweep",
                            partial(serving.kernel_sweep, steps=4))
        return partial(serving.serve_sweep, quick=True, steps=6)
    if name == "robust":
        monkeypatch.setattr(robustness, "desim_sweep",
                            partial(robustness.desim_sweep, r=300))
        monkeypatch.setattr(robustness, "store_sweep",
                            partial(robustness.store_sweep, steps=8))
        return partial(robustness.robust_sweep, quick=True)
    if name == "scale":
        monkeypatch.setattr(scaling, "C_SWEEP", (1, 2))
        monkeypatch.setattr(scaling, "MODULE_SWEEP", (4,))
        monkeypatch.setattr(scaling, "desim_scaling",
                            partial(scaling.desim_scaling, r=300))
        monkeypatch.setattr(scaling, "store_scaling",
                            partial(scaling.store_scaling, steps=8))
        return partial(scaling.scale_sweep, quick=True)
    monkeypatch.setattr(capacity, "FRACS", (0.05, 0.10, 0.20))
    monkeypatch.setattr(capacity, "POLICY_NAMES", ("lru", "fifo"))
    monkeypatch.setattr(capacity, "desim_capacity",
                        partial(capacity.desim_capacity, r=300))
    monkeypatch.setattr(capacity, "store_capacity",
                        partial(capacity.store_capacity, steps=8))
    return partial(capacity.capacity_sweep, quick=True)


@pytest.mark.parametrize("name", ["serve", "robust", "scale", "capacity"])
def test_checked_in_bench_jsons_match_producer_schema(name, monkeypatch):
    """What each BENCH_*.json producer writes today carries only keys
    `benchmarks.validate.BENCH_SCHEMAS` allows, and every key the schema
    names at the top: the producer runs here, in-process and small, so a
    schema that drifts from its producer fails on a clean tree (the
    artifacts themselves are build outputs, not checked in)."""
    from benchmarks.validate import BENCH_SCHEMAS, assert_bench_schema
    doc = _tiny_producer(name, monkeypatch)()
    fname = f"BENCH_{name}.json"
    assert_bench_schema(fname, json.loads(json.dumps(doc)))
    assert set(doc) == BENCH_SCHEMAS[fname]["top"] - {"mesh"}, fname
