"""The store's request loop (`daemon_store._schedule`) visits only a
step's misses where the §4.1 ratio is static, and every request where it
adapts (`daemon_store._request_order`).

Each stepper, for both page layouts, is run over a stream with all-miss,
mixed and all-hit steps and compared with a replay in which the loop
visits every request, as a scan over all R requests does. Every state
leaf, served row and hit flag is bit-equal, except the fabric's (and the
NICs') offered-demand EMAs: the change decays them by
`(1 - EMA_ALPHA) ** hits` at once where the full scan rounds once a hit,
so they agree to rtol 1e-4 (float32 rounding over up to ~1,000 hits)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daemon_store as ds
from repro.core.fabric import FabricConfig

R = 6                 # requests a store a step
PAGES = 24            # pages a store owns in the tier
EMA_LEAVES = ("line_rate", "page_rate")
# per step, the pages a store reads (offsets into its own PAGES): fresh
# pages miss, re-read ones hit once they have landed, and the two kinds
# mix where a step reads both; the pool holds 8 pages
PATTERN = [[0, 1, 2, 3, 4, 5], [0, 1, 2, 3, 4, 5], [0, 1, 2, 3, 4, 5],
           [0, 1, 2, 8, 9, 10], [0, 1, 2, 8, 9, 10], [0, 2, 8, 9, 10, 1],
           [12, 13, 14, 15, 16, 17], [12, 13, 14, 15, 16, 17],
           [17, 16, 15, 14, 13, 12], [12, 13, 20, 21, 14, 15]]


def _cfg(layout, adaptive=False):
    kw = (dict(latent_dim=128, page_tokens=16) if layout == "latent"
          else dict(kv_heads=2, head_dim=16, page_tokens=8))
    return ds.KVStoreConfig(num_local_pages=8, page_budget_per_step=64,
                            adaptive_ratio=adaptive,
                            fabric=FabricConfig(num_modules=4), **kw)


def _stream(stores, seed=3):
    """(T, stores, R) pages, offsets and writes: PATTERN on each store's
    own pages, with writes on a third of the requests."""
    rng = np.random.default_rng(seed)
    base = (np.arange(stores) * PAGES)[None, :, None]
    pages = (np.asarray(PATTERN)[:, None, :] + base).astype(np.int32)
    offs = rng.integers(0, 64, size=pages.shape).astype(np.int32)
    return pages, offs, rng.random(pages.shape) < 0.3


def _tier(cfg, stores, seed=4):
    rng = np.random.default_rng(seed)
    shape = (stores * PAGES,) + cfg.page_row()
    make = lambda: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return make(), None if cfg.latent_dim else make()


def _run(cfg, stepper, stores=4):
    """Step `stepper` through the stream. Returns per step (state, k, v,
    hit (stores, R))."""
    pages, offs, writes = _stream(stores)
    rk, rv = _tier(cfg, stores)
    if stepper == "single":
        state = ds.init_kv_store(cfg)
        step = jax.jit(lambda s, p, o, w: ds.step_fetch(
            s, cfg, rk, rv, p[0], o[0], w[0]))
    elif stepper == "batch":
        state = ds.init_kv_store_batch(cfg, stores)
        step = jax.jit(lambda s, p, o, w: ds.step_fetch_batch(
            s, cfg, rk, rv, p, o, w))
    else:      # two replicas: the NIC leg is active
        state = ds.init_kv_store_replicated(cfg, 2, stores // 2)
        shape = (2, stores // 2, R)
        step = jax.jit(lambda s, p, o, w: ds.step_fetch_replicated(
            s, cfg, rk, rv, p.reshape(shape), o.reshape(shape),
            w.reshape(shape)))
    out = []
    for t in range(pages.shape[0]):
        state, k, v, hit = step(state, pages[t], offs[t], writes[t])
        out.append((state, k, v, np.asarray(hit).reshape(-1, R)))
    return out


def _visit_all(cfg, needed_pages, local_hit):
    r = needed_pages.shape[0]
    return jnp.arange(r), r, None


def _full_scan(cfg, stepper, stores=4):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ds, "_request_order", _visit_all)
        return _run(cfg, stepper, stores)


def _banks(state):
    return {"fab": state.fab, "nic": getattr(state, "nic", None)}


def _seqs(state):
    return state.seq if isinstance(state, ds.KVStoreState) else state.seqs


def _equal(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _check_step(got, want, visited, visited0, ema_rtol=1e-4):
    """One step's outputs and state against the full scan's: bit-equal
    but for the EMAs (to `ema_rtol`); `request_iterations` equals
    `visited` and, in the full scan, `visited0`."""
    (s, k, v, hit), (s0, k0, v0, hit0) = got, want
    _equal(hit, hit0)
    jax.tree.map(_equal, (k, v), (k0, v0))
    seq, seq0 = _seqs(s), _seqs(s0)
    stats = dict(seq.stats)
    stats0 = dict(seq0.stats)
    _equal(stats.pop("request_iterations"), visited)
    _equal(stats0.pop("request_iterations"), visited0)
    jax.tree.map(_equal, seq._replace(stats=stats),
                 seq0._replace(stats=stats0))
    _equal(s.clock, s0.clock)
    for name, bank in _banks(s).items():
        bank0 = _banks(s0)[name]
        if bank is None:
            assert bank0 is None
            continue
        for leaf in bank._fields:
            a, b = getattr(bank, leaf), getattr(bank0, leaf)
            if leaf in EMA_LEAVES:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=ema_rtol, atol=0)
            else:
                jax.tree.map(_equal, a, b)


@pytest.mark.parametrize("layout", ["kv", "latent"])
@pytest.mark.parametrize("stepper", ["single", "batch", "replicated"])
def test_request_loop_matches_full_scan(stepper, layout):
    """Static ratio: the loop runs once a miss, and the step is the full
    scan's."""
    cfg = _cfg(layout)
    stores = 1 if stepper == "single" else 4
    got = _run(cfg, stepper, stores)
    want = _full_scan(cfg, stepper, stores)
    shape = () if stepper == "single" else (stores,)
    kinds, misses = set(), np.zeros(stores, np.float32)
    for t, (g, w) in enumerate(zip(got, want)):
        hit = g[3]
        kinds.add("hit" if hit.all() else "miss" if not hit.any()
                  else "mixed")
        misses += (~hit).sum(axis=1)
        _check_step(g, w, misses.reshape(shape),
                    np.full(shape, (t + 1) * R, np.float32))
    assert kinds == {"hit", "miss", "mixed"}
    # the EMAs the closed form decays were live
    assert float(jnp.max(got[-1][0].fab.line_rate)) > 0.0


@pytest.mark.parametrize("stepper", ["single", "batch", "replicated"])
def test_request_loop_visits_every_request_under_adaptive_ratio(stepper):
    """Adaptive ratio: a hit moves its module's ratio, so the loop runs
    once a request and the step — ratio and EMAs included — is the full
    scan's, bit for bit."""
    cfg = _cfg("kv", adaptive=True)
    stores = 1 if stepper == "single" else 4
    got = _run(cfg, stepper, stores)
    want = _full_scan(cfg, stepper, stores)
    shape = () if stepper == "single" else (stores,)
    for t, (g, w) in enumerate(zip(got, want)):
        every = np.full(shape, (t + 1) * R, np.float32)
        _check_step(g, w, every, every, ema_rtol=0)
    ratio = np.asarray(got[-1][0].fab.ratio)
    assert (np.abs(ratio - cfg.daemon.bw_ratio) > 1e-4).any()


def test_request_order_packs_misses_and_counts_hits_between():
    """Misses first, in request order, padded with R; the hits before
    each miss since the previous one, and after the last, by module
    (interleaved placement over 4 modules: page % 4)."""
    cfg = _cfg("kv")
    pages = jnp.asarray([4, 9, 2, 6, 7, 8, 1, 3], jnp.int32)
    hit = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0], bool)
    order, n, gap = ds._request_order(cfg, pages, hit)
    _equal(order, [1, 4, 7, 8, 8, 8, 8, 8, 8])
    assert int(n) == 3
    _equal(gap, [[1, 0, 0, 0],       # page 4 before miss 1
                 [0, 0, 2, 0],       # pages 2, 6 before miss 4
                 [1, 1, 0, 0],       # pages 8, 1 before miss 7
                 [0, 0, 0, 0]]       # none after it
           + [[0, 0, 0, 0]] * 5)
    order, n, gap = ds._request_order(_cfg("kv", adaptive=True), pages, hit)
    _equal(order, np.arange(8))
    assert n == 8 and gap is None
