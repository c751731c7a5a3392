"""Fused residency transaction: Pallas kernel (interpret) vs jnp oracle,
fused-vs-chain store bit-identity, compile counts, BENCH schema."""
import json
from pathlib import Path

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


def test_checked_in_bench_jsons_match_producer_schema():
    """Every committed BENCH_*.json must carry only keys its producer
    still writes — a stale artifact (old keys) fails here instead of a
    reader trusting a dead column (benchmarks.validate.BENCH_SCHEMAS)."""
    from benchmarks.validate import assert_bench_schema
    root = Path(__file__).resolve().parent.parent
    found = sorted(root.glob("BENCH_*.json"))
    assert found, "no BENCH_*.json checked in at repo root"
    for path in found:
        assert_bench_schema(path.name, json.loads(path.read_text()))
