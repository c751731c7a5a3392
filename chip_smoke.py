"""Chip smoke test: the system's user-facing paths on a TPU, in one process.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: the mesh plane only

Phases, each through the entry point a user calls, at real sizes:
  (a) store hot path — `daemon_store.step_fetch_batch` at the
      benchmark's hot-path configuration (B=64 tenants, 256x16 pools,
      8192-page remote regions, 48 steps), once with the fused Pallas
      kernel and once with its jnp oracle on the same streams: the
      integer ledger, the final store state and every served page must
      match exactly, and the compiled Pallas step must call the kernel;
      then the same streams with writes into small FIFO pools, so that
      evictions, dirty victims and writebacks run through the kernel;
  (b) simulator — `desim.simulate_lattice` against the golden capture
      (counts exact, simulated times within 1e-3 relative), then one
      lattice of every scheme at the benchmark's 60000-request length;
  (c) paged serving — `serve_loop.serve_batch_paged` with qwen3-1.7b at
      its published width (random weights from --seed), the store's
      pages carrying the model's KV rows: greedy tokens equal
      `serve_batch`, per-module bytes sum exactly to wire + writeback.
With --chips 4 only the mesh plane runs: the sharded lattice against the
one-chip lattice (bit-identical) and the replica-sharded store against
the one-chip replicated store (same requests, exact byte conservation,
wire bytes within 1%).

Per-phase results and timings go to earlier lines; the last line is one
JSON object naming the device. Any failed check raises, so the exit code
is non-zero. Without a TPU, or outside a checkout of the repo, the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
GOLDEN = ROOT / "tests" / "golden" / "seed_movement_golden.json"

if not (ROOT / "src" / "repro").is_dir():
    sys.exit("chip_smoke.py: run it from a checkout of the repo "
             "(src/repro not found next to it)")
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.daemon_store import (KVStoreConfig,  # noqa: E402
                                     init_kv_store_batch,
                                     init_kv_store_replicated, ledger,
                                     step_fetch_batch,
                                     step_fetch_replicated)
from repro.core.params import NetworkParams  # noqa: E402
from repro.kernels import ops, residency_fused  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import init_model  # noqa: E402
from repro.runtime.serve_loop import (ServeConfig,  # noqa: E402
                                      serve_batch, serve_batch_paged)
from repro.sim.desim import (SimConfig, make_net,  # noqa: E402
                             simulate_lattice)
from repro.sim.schemes import SCHEMES  # noqa: E402
from repro.sim.trace import generate_trace  # noqa: E402
from repro.sim.workloads import WORKLOADS  # noqa: E402

# golden-capture fields: counts must match exactly, the rest (simulated
# times and the ratios derived from them) within TIME_RTOL
COUNT_KEYS = ("lines_moved", "pages_moved", "page_drops", "net_bytes")
TIME_RTOL = 1e-3
LEDGER_KEYS = ("requests", "local_hits", "page_moves", "evictions",
               "wire_bytes", "writeback_bytes", "module_bytes")


def check(cond, detail="") -> None:
    """A smoke check that holds under `python -O` too."""
    if not cond:
        raise AssertionError(detail)


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _tree_equal(a, b, what: str) -> None:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    check(len(la) == len(lb), what)
    for i, (x, y) in enumerate(zip(la, lb)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            raise AssertionError(f"{what}: leaf {i} differs")


# --------------------------------------------------------- (a) store
def _store_run(cfg, pages, offs, writes, rk, rv):
    """Compile one `step_fetch_batch` and drive it over the streams.
    Returns (compiled HLO text, final state, per-step hits, last k/v,
    compile s, wall s)."""
    step = jax.jit(lambda st, rk, rv, need, off, wr: step_fetch_batch(
        st, cfg, rk, rv, need, off, wr))
    state = init_kv_store_batch(cfg, pages.shape[1])
    t0 = time.perf_counter()
    compiled = step.lower(state, rk, rv, pages[0], offs[0],
                          writes[0]).compile()
    compile_s = time.perf_counter() - t0
    hits = []
    t0 = time.perf_counter()
    for t in range(pages.shape[0]):
        state, k, v, hit = compiled(state, rk, rv, pages[t], offs[t],
                                    writes[t])
        hits.append(hit)
    jax.block_until_ready((state, k, v))
    wall_s = time.perf_counter() - t0
    return (compiled.as_text(), state,
            np.stack([np.asarray(h) for h in hits]), (k, v), compile_s,
            wall_s)


def _pallas_vs_ref(label, cfg, pages, offs, writes, rk, rv) -> dict:
    runs = {}
    for impl in ("pallas", "ref"):
        text, state, hits, kv_last, c_s, w_s = _store_run(
            replace(cfg, kernel_impl=impl), pages, offs, writes, rk, rv)
        called = residency_fused.in_compiled(text)
        check(called == (impl == "pallas"),
              f"{label} kernel_impl={impl}: kernel custom call {called}")
        led = ledger(state)
        runs[impl] = (led, state, hits, kv_last)
        log(f"[a] {label} impl={impl} kernel_in_hlo={called} "
            f"B={pages.shape[1]} pool={cfg.pool_geometry()} "
            f"steps={pages.shape[0]} compile_s={c_s:.3f} wall_s={w_s:.3f} "
            + " ".join(f"{k}={led[k]:.0f}" for k in LEDGER_KEYS[:-1]))
    (lp, sp, hp, kvp), (lr, sr, hr, kvr) = runs["pallas"], runs["ref"]
    for key in LEDGER_KEYS:
        check(lp[key] == lr[key], (label, key, lp[key], lr[key]))
    np.testing.assert_array_equal(hp, hr, err_msg=f"{label}: local hits")
    _tree_equal(sp, sr, f"{label}: final store state")
    _tree_equal(kvp, kvr, f"{label}: last step's served k/v")
    log(f"[a] {label}: pallas == ref (ledger, store state, hits, "
        f"served pages)")
    return lp


def phase_store(seed: int, steps: int = 48) -> None:
    """The benchmark's hot path as it is (read-only streams into 4096-page
    pools that 48 steps cannot fill), then the same tenants and streams
    with 30% KV-append writes into 16-page FIFO pools (4 sets x 4 ways)
    so that victims are resident, written pages get evicted dirty and
    writebacks flow (the fabric lands only ~27 pages per tenant in 48
    steps, fewer than a 64-page pool holds)."""
    from benchmarks.serving import (KERNEL_BATCH, KERNEL_PAGES_PER_TENANT,
                                    _kernel_cfg, _kernel_streams)
    pages, offs = _kernel_streams(steps)
    cfg = _kernel_cfg("pallas")
    n_remote = KERNEL_BATCH * KERNEL_PAGES_PER_TENANT
    shape = (n_remote, cfg.page_tokens, cfg.kv_heads, cfg.head_dim)
    kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    rk = jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
    rv = jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16)
    pages, offs = jnp.asarray(pages), jnp.asarray(offs)
    no_writes = jnp.zeros(pages.shape, bool)
    led = _pallas_vs_ref("hot path", cfg, pages, offs, no_writes, rk, rv)
    check(led["requests"] > 0 and led["page_moves"] > 0, led)
    writes = jax.random.bernoulli(kw, 0.3, pages.shape)
    small = replace(cfg, num_local_pages=16, pool_ways=4, policy="fifo")
    led = _pallas_vs_ref("pressure", small, pages, offs, writes, rk, rv)
    check(led["evictions"] > 0 and led["writeback_bytes"] > 0, led)
    log("[a] PASS")


# ----------------------------------------------------- (b) simulator
def _nets(pairs):
    return [make_net(NetworkParams(bw_factor=bf, switch_latency_ns=sw))
            for sw, bf in pairs]


def phase_sim(full_r: int = 60000) -> None:
    golden = json.loads(GOLDEN.read_text())
    names = golden["schemes"]
    nets = _nets(golden["net_pairs"])
    max_rel, worst = 0.0, None
    for wl, rec in golden["workloads"].items():
        tr = generate_trace(WORKLOADS[wl], golden["r"], seed=rec["seed"])
        res, w_s = _timed(simulate_lattice, [SCHEMES[s] for s in names],
                          SimConfig(), tr, nets, rec["comp_ratio"])
        for i, s in enumerate(names):
            for j in range(len(nets)):
                for key, old in rec["schemes"][s][j].items():
                    new = res[i][j][key]
                    if key in COUNT_KEYS:
                        check(new == old, (wl, s, j, key, new, old))
                        continue
                    rel = abs(new - old) / max(abs(old), 1e-30)
                    if rel > max_rel:
                        max_rel, worst = rel, (wl, s, j, key, new, old)
        log(f"[b] golden {wl}: r={golden['r']} {len(names)} schemes x "
            f"{len(nets)} nets wall_s={w_s:.3f} (compile included)")
    log(f"[b] golden max relative difference (non-count fields) "
        f"{max_rel!r} at {worst}")
    check(max_rel <= TIME_RTOL, (max_rel, worst))
    wl = "pr"
    tr = generate_trace(WORKLOADS[wl], full_r, seed=golden["workloads"][
        wl]["seed"])
    schemes = list(SCHEMES.values())
    res, w_s = _timed(simulate_lattice, schemes, SimConfig(), tr, nets,
                      WORKLOADS[wl].comp_ratio)
    for i, row in enumerate(res):
        for j, m in enumerate(row):
            check(all(math.isfinite(v) for v in m.values()), (i, j, m))
            check(0.0 <= m["hit_ratio"] <= 1.0, (i, j, m))
            check(m["total_time_ns"] > 0, (i, j, m))
    log(f"[b] full lattice {wl}: r={full_r} {len(schemes)} schemes x "
        f"{len(nets)} nets wall_s={w_s:.3f} (compile included) "
        f"daemon total_time_ns="
        f"{res[list(SCHEMES).index('daemon')][0]['total_time_ns']!r}")
    log("[b] PASS golden capture matched, full-length lattice finite")


# --------------------------------------------------- (c) paged serving
def phase_serve(seed: int, arch: str = "qwen3-1.7b", batch: int = 4,
                prompt_len: int = 16, new_tokens: int = 16) -> None:
    cfg = get_config(arch)
    t0 = time.perf_counter()
    params, _ = init_model(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[c] {arch} full width: {n_params} params, init_s="
        f"{time.perf_counter() - t0:.3f}")
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 2,
                                 min(1000, cfg.vocab_size), jnp.int32)
    scfg = ServeConfig(max_new_tokens=new_tokens, seed=seed)
    store_cfg = KVStoreConfig(
        num_local_pages=16, page_tokens=16, kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, page_budget_per_step=8, kernel_impl="auto")
    impl = ops.resolve_impl(store_cfg.kernel_impl)
    check(impl == "pallas", impl)
    ref_tokens, s_dense = _timed(serve_batch, params, cfg, prompts, scfg)
    (tokens, led), s_paged = _timed(serve_batch_paged, params, cfg,
                                    prompts, scfg, store_cfg)
    log(f"[c] serve_batch wall_s={s_dense:.3f}; serve_batch_paged "
        f"impl={impl} wall_s={s_paged:.3f} (compiles included)")
    tokens, ref_tokens = np.asarray(tokens), np.asarray(ref_tokens)
    check(tokens.shape == (batch, prompt_len + new_tokens), tokens.shape)
    np.testing.assert_array_equal(tokens, ref_tokens,
                                  err_msg="paged vs dense greedy tokens")
    moved = led["wire_bytes"] + led["writeback_bytes"]
    module_total = sum(led["module_bytes"])
    log(f"[c] tokens[0]={tokens[0].tolist()} requests="
        f"{led['requests']:.0f} local_hits={led['local_hits']:.0f} "
        f"page_moves={led['page_moves']:.0f} wire_bytes="
        f"{led['wire_bytes']:.0f} writeback_bytes="
        f"{led['writeback_bytes']:.0f} module_bytes_sum={module_total:.0f}")
    check(led["requests"] > 0 and led["wire_bytes"] > 0, led)
    check(module_total == moved, (module_total, moved))
    log("[c] PASS tokens == serve_batch, module bytes == wire + writeback")


# ---------------------------------------------------- --chips 4: mesh
def phase_mesh(n_dev: int, seed: int) -> None:
    from repro.runtime import mesh_plane
    golden = json.loads(GOLDEN.read_text())
    wl = "pr"
    tr = generate_trace(WORKLOADS[wl], golden["r"],
                        seed=golden["workloads"][wl]["seed"])
    nets = _nets(golden["net_pairs"])
    schemes = list(SCHEMES.values())
    pols = ["lru", "fifo"]
    mesh = mesh_plane.make_data_mesh(n_dev)
    one, s_one = _timed(simulate_lattice, schemes, SimConfig(), tr, nets,
                        WORKLOADS[wl].comp_ratio, policies=pols)
    got, s_mesh = _timed(mesh_plane.simulate_lattice_sharded, schemes,
                         SimConfig(), tr, nets, WORKLOADS[wl].comp_ratio,
                         mesh=mesh, policies=pols)
    cells = 0
    for i in range(len(schemes)):
        for j in range(len(nets)):
            for p in range(len(pols)):
                for k, v in one[i][j][p].items():
                    g = got[i][j][p][k]
                    check(v == g or (np.isnan(v) and np.isnan(g)),
                          (i, j, p, k, v, g))
                cells += 1
    log(f"[mesh] lattice {len(nets)} nets x {len(pols)} policies on "
        f"{n_dev} chips: {cells} scheme cells bit-identical to one chip; "
        f"one_chip_s={s_one:.3f} sharded_s={s_mesh:.3f} "
        f"(compiles included)")

    c, b, r, steps = n_dev, 8, 4, 32
    cfg = KVStoreConfig(num_local_pages=16, page_tokens=16, kv_heads=8,
                        head_dim=128, page_budget_per_step=16)
    n_remote = c * b * 32
    shape = (n_remote, cfg.page_tokens, cfg.kv_heads, cfg.head_dim)
    rk = jax.random.normal(jax.random.PRNGKey(seed), shape,
                           jnp.float32).astype(jnp.bfloat16)
    key = jax.random.PRNGKey(seed + 1)
    st = mesh_plane.shard_replicated_state(
        init_kv_store_replicated(cfg, c, b), mesh)
    ref_st = init_kv_store_replicated(cfg, c, b)
    one_step = jax.jit(lambda s, need, off, wr: step_fetch_replicated(
        s, cfg, rk, rk, need, off, wr))
    t_mesh = t_one = 0.0
    for _ in range(steps):
        key, k1, k2, k3 = jax.random.split(key, 4)
        need = jax.random.randint(k1, (c, b, r), 0, n_remote)
        offs = jax.random.randint(k2, (c, b, r), 0, cfg.page_tokens)
        wrs = jax.random.bernoulli(k3, 0.3, (c, b, r))
        out, dt = _timed(mesh_plane.step_replicated_sharded, st, cfg,
                         mesh, rk, rk, need, offs, wrs)
        st, t_mesh = out[0], t_mesh + dt
        out, dt = _timed(one_step, ref_st, need, offs, wrs)
        ref_st, t_one = out[0], t_one + dt
    led, led_ref = ledger(st), ledger(ref_st)
    moved = led["wire_bytes"] + led["writeback_bytes"]
    module_total = sum(led["module_bytes"])
    log(f"[mesh] store C={c} replicas x B={b} on {n_dev} chips, {steps} "
        f"steps: requests={led['requests']:.0f} (one chip "
        f"{led_ref['requests']:.0f}) wire_bytes={led['wire_bytes']:.0f} "
        f"(one chip {led_ref['wire_bytes']:.0f}) module_bytes_sum="
        f"{module_total:.0f} unit_bytes_sum={sum(led['unit_bytes']):.0f} "
        f"wire+wb={moved:.0f} sharded_s={t_mesh:.3f} one_chip_s="
        f"{t_one:.3f} (compiles included)")
    check(led["requests"] == led_ref["requests"],
          (led["requests"], led_ref["requests"]))
    check(module_total == moved, (module_total, moved))
    check(sum(led["unit_bytes"]) == moved, (led["unit_bytes"], moved))
    check(abs(led["wire_bytes"] - led_ref["wire_bytes"])
          <= 0.01 * led_ref["wire_bytes"],
          (led["wire_bytes"], led_ref["wire_bytes"]))
    log("[mesh] PASS sharded lattice bit-identical, sharded store "
        "conserves bytes")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU found (JAX sees "
                 f"{devs[0].platform!r} devices); nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but JAX sees "
                 f"{len(devs)} TPU devices")
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(4, args.seed)
    else:
        for name, fn, fargs in (("a", phase_store, (args.seed,)),
                                ("b", phase_sim, ()),
                                ("c", phase_serve, (args.seed,))):
            t1 = time.perf_counter()
            fn(*fargs)
            log(f"[{name}] phase wall_s={time.perf_counter() - t1:.3f}")
    log(f"total wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
