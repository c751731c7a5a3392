"""The plain references agree with the program at sizes a test run holds
(on the CPU), and the control, one precision down, does not."""
import json

import numpy as np
import pytest

import traffic
from benchtest_support import BENCH
from reference import desim_ref, store_ref

SIM = json.loads((BENCH / "configs" / "daemon-paper-sim.json").read_text())
KV = json.loads((BENCH / "configs" / "kv-tier-olmoe-1b-7b.json").read_text())


@pytest.mark.parametrize("scheme", ["remote", "cl+page", "daemon",
                                    "daemon-adaptive", "page-free"])
def test_desim_reference_matches_program(scheme):
    from repro.core.params import DaemonParams, NetworkParams
    from repro.sim import desim
    from repro.sim.schemes import SchemeFlags
    wl = SIM["workloads"]["pr"]
    tr = traffic.llc_miss_trace(wl, "pr", 1200, 2**31 + 17)
    net = (400.0, 8.0)
    got = desim.simulate_lattice(
        [SchemeFlags(name=scheme, **SIM["schemes"][scheme])],
        desim.SimConfig(daemon=DaemonParams(**SIM["daemon"])), tr,
        [desim.make_net(NetworkParams(bw_factor=net[1],
                                      switch_latency_ns=net[0]))],
        wl["comp_ratio"])[0][0]
    ref = desim_ref.simulate(SIM, SIM["schemes"][scheme], net, tr,
                             wl["comp_ratio"])
    for k, v in ref.items():
        assert got[k] == v, (k, got[k], v)


def _random_streams(tenants, steps, seed):
    """Skewed page reads with writes anywhere in a page: misses, landings,
    evictions and dirty writebacks in every run, to hold the reference to
    the program on every path."""
    rng = np.random.default_rng(seed)
    shape = (steps, tenants, 4)
    rank = rng.zipf(1.3, size=shape).clip(1, 256)
    pages = (rank - 1 + (np.arange(tenants) * 256)[None, :, None])
    offs = rng.integers(0, 16, size=shape).astype(np.int32)
    return pages.astype(np.int32), offs, rng.random(shape) < 0.3


def _streams(mix, tenants, steps, seed):
    if mix == "random":
        return _random_streams(tenants, steps, seed)
    m = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    m.update(tenants=tenants, stream_steps=steps)
    return traffic.store_streams(m, 256, 16, seed)


def _store_run(cfg, pages, offs, writes):
    import jax
    import jax.numpy as jnp
    import harness
    store_config = harness.load_module(
        BENCH / "entries" / "store_batch.py", "t_store").store_config
    from repro.core import daemon_store
    kcfg = store_config(cfg)
    b = pages.shape[1]
    n = b * cfg["store"]["context_tokens"] // cfg["store"]["page_tokens"]
    rk = jnp.zeros((n, kcfg.page_tokens, kcfg.kv_heads, kcfg.head_dim),
                   jnp.bfloat16)
    step = jax.jit(lambda s, p, o, w: daemon_store.step_fetch_batch(
        s, kcfg, rk, rk, p, o, w))
    st = daemon_store.init_kv_store_batch(kcfg, b)
    hits = []
    for t in range(pages.shape[0]):
        st, _, _, h = step(st, pages[t], offs[t], writes[t])
        hits.append(np.asarray(h))
    return st, np.stack(hits)


@pytest.mark.parametrize("mix,pool", [("random", 48), ("random", 16),
                                      ("window-b64", 48)])
def test_store_reference_matches_program(mix, pool):
    from repro.core import daemon_store
    cfg = json.loads(json.dumps(KV))
    cfg["model"]["num_key_value_heads"] = 1
    cfg["store"]["local_pages_per_tenant"] = pool
    pages, offs, writes = _streams(mix, 3, 60, 2**31 + 1)
    st, hits = _store_run(cfg, pages, offs, writes)
    ref = store_ref.run(store_ref.StoreSpec(cfg, 3), pages, offs, writes)
    led = daemon_store.ledger(st)
    np.testing.assert_array_equal(hits, ref["hits"])
    np.testing.assert_array_equal(np.asarray(st.seqs.res.page),
                                  ref["tables"])
    for k in store_ref.LEDGER_KEYS:
        assert led[k] == ref["ledger"][k], k
    assert led["module_bytes"] == ref["module_bytes"]
    if pool == 16:
        assert ref["ledger"]["dirty_evicts"] > 0


def test_store_control_in_lower_precision_differs():
    from lowered import bf16_round
    cfg = json.loads(json.dumps(KV))
    pages, offs, writes = _random_streams(3, 80, 5)
    spec = store_ref.StoreSpec(cfg, 3)
    a = store_ref.run(spec, pages, offs, writes)
    b = store_ref.run(spec, pages, offs, writes, rnd=bf16_round)
    assert a["ledger"] != b["ledger"]
