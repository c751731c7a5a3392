"""Entry: the batched two-tier KV store (`daemon_store.step_fetch_batch`).

Each layer of each tenant is a store of its own (tenants x layers stores
side by side on the batch axis, one region of the remote tier each), as
a tier that keeps every layer's KV in pages of its own holds them. One
unit of work is one decode step of every tenant: the compiled step (the
fused residency transaction, the remote critical fetch and the
scheduling fold over all stores' requests on the shared fabric) is
dispatched and its served k/v awaited, since decode needs its pages. The
remote tier holds bfloat16 rows made on the device from the seed
(`bench/remote_tier.py`); request streams come from the mix.

The check, after the window: the plain reference
(`bench/reference/store_ref.py`) replays every step that ran and must
agree on each step's hit flags (the last `stream_steps` of them, which
the device keeps), the final page tables and the integer
ledger with the per-module wire bytes (so bytes are conserved as the
reference conserves them); the served k/v of steps sampled from the
seed, and every resident pool slot, must equal the tier's rows by
formula.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from lowered import bf16_round
from reference import store_ref
import remote_tier
import traffic

# Every number is an exact comparison (limit 0): the store's decisions
# are integer and boolean, and its float32 times are formed as the
# reference forms them; readings in PERF.md.
LIMITS = {
    "served_rows_wrong": 0.0,      # served k/v rows != the tier's row
    "pool_rows_wrong": 0.0,        # resident slots != the tier's row
    "hits_gap": 0.0,               # share of per-step hit flags that differ
    "tables_gap": 0.0,             # share of final page-table entries
    "ledger_gap": 0.0,             # worst relative gap of a ledger entry
}
# the control: the reference with its simulated times in bfloat16 and its
# pages passed through float8 e4m3
CONTROL = (bf16_round, "float8_e4m3fn")
# pool slots compared against the tier's rows per call of the check
CHECK_BLOCK = 2048


def store_config(cfg):
    from repro.core.daemon_store import KVStoreConfig
    from repro.core.fabric import FabricConfig
    from repro.core.params import DaemonParams
    st, m = cfg["store"], cfg["model"]
    pages_per_tenant = st["context_tokens"] // st["page_tokens"]
    return KVStoreConfig(
        num_local_pages=st["local_pages_per_tenant"],
        page_tokens=st["page_tokens"], kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], daemon=DaemonParams(**cfg["daemon"]),
        compress_pages=st["compress_pages"],
        page_budget_per_step=st["page_budget_per_step"],
        selection=st["selection"], adaptive_ratio=st["adaptive_ratio"],
        fabric=FabricConfig(num_modules=st["memory_modules"],
                            placement=st["placement"],
                            affinity_block=pages_per_tenant),
        policy=st["policy"], pool_ways=st["pool_ways"], kernel_impl="auto")


class Cell:
    def __init__(self, cfg, mix, seed, devs, scale):
        self.cfg = dict(cfg, store=dict(cfg["store"]), model=dict(
            cfg["model"]))
        self.cfg["model"].update(scale.get("model", {}))
        self.cfg["store"].update(scale.get("store", {}))
        self.mix = dict(mix, **scale.get("mix", {}))
        self.seed, self.devs = seed, devs
        st, m = self.cfg["store"], self.cfg["model"]
        self.B = self.mix["tenants"]
        self.layers = m["num_hidden_layers"]
        self.S = self.B * self.layers          # stores
        self.pages_per_tenant = st["context_tokens"] // st["page_tokens"]
        self.row = (st["page_tokens"], m["num_key_value_heads"],
                    m["head_dim"])
        self.trace_units = self.mix["trace_units"]
        self.rng = np.random.default_rng([seed, 2])
        self.salts = [remote_tier.seed_words(seed, w) for w in (0, 1)]
        self._failed = 0

    # ------------------------------------------------------------ set-up
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core import daemon_store

        t0 = time.perf_counter()
        dev = self.devs[0]
        self.kcfg = kcfg = store_config(self.cfg)
        pages, offs, writes = traffic.store_streams(
            self.mix, self.pages_per_tenant, self.cfg["store"]["page_tokens"],
            self.seed, self.layers)
        self.streams = (pages, offs, writes)
        self.T = pages.shape[0]
        n_remote = self.S * self.pages_per_tenant
        row = self.row

        @jax.jit
        def make_tier(salts):
            ids = jnp.arange(n_remote, dtype=jnp.uint32)
            return tuple(remote_tier.rows(ids, row, salts[w], jnp)
                         for w in (0, 1))

        salts = jax.device_put(np.asarray(self.salts, np.uint32), dev)
        self.rk, self.rv = make_tier(salts)
        self.dev_streams = jax.device_put(
            (pages, offs, writes), dev)

        T = self.T

        def step(state, t, hits, rk, rv, p, o, w):
            # the stream position rides on the device with the state, and
            # each step's hit flags land in a buffer there, so a step
            # costs the host one dispatch and one wait
            pick = lambda x: jax.lax.dynamic_index_in_dim(x, t,
                                                          keepdims=False)
            out, k, v, hit = daemon_store.step_fetch_batch(
                state, kcfg, rk, rv, pick(p), pick(o), pick(w))
            hits = jax.lax.dynamic_update_index_in_dim(hits, hit, t, 0)
            return out, (t + 1) % T, hits, k, v

        state = jax.device_put(daemon_store.init_kv_store_batch(kcfg,
                                                                self.S), dev)
        pos = jax.device_put(np.int32(0), dev)
        hits = jax.device_put(np.zeros(pages.shape, bool), dev)
        t1 = time.perf_counter()
        self.compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            state, pos, hits, self.rk, self.rv, *self.dev_streams).compile()
        t2 = time.perf_counter()
        self.state, self.pos, self.hit_buf = state, pos, hits
        self.t = 0
        self.step_s, self.step_cpu_s, self.step_gcs = [], [], []
        self.step_at = []
        self.gcs = 0
        gc.callbacks.append(self._count_gc)
        self.kept = []             # (step, k, v) sampled from the window
        self.replaced = []         # steps at which the sample changed
        self.seen = 0
        for _ in range(self.mix["warm_steps"]):
            self._step()
        self.step_s, self.step_cpu_s, self.step_gcs = [], [], []
        self.step_at = []
        self.phases = {"inputs_s": t1 - t0, "compile_s": t2 - t1,
                       "warm_s": time.perf_counter() - t2}
        self.led0 = daemon_store.ledger(self.state)

    def _count_gc(self, phase, info):
        if phase == "start":
            self.gcs += 1

    def _step(self):
        import jax
        from jax import profiler
        t0, c0, g0 = time.perf_counter(), time.process_time(), self.gcs
        with profiler.TraceAnnotation("bench.step.dispatch"):
            (self.state, self.pos, self.hit_buf, k, v) = self.compiled(
                self.state, self.pos, self.hit_buf, self.rk, self.rv,
                *self.dev_streams)
        with profiler.TraceAnnotation("bench.step.wait"):
            jax.block_until_ready((k, v))
        self.step_s.append(time.perf_counter() - t0)
        self.step_cpu_s.append(time.process_time() - c0)
        self.step_gcs.append(self.gcs - g0)
        self.step_at.append(time.time())
        self.t += 1
        return k, v

    # ------------------------------------------------------------ window
    def unit(self):
        k, v = self._step()
        # reservoir sample of the window's steps, drawn from the seed
        n = self.mix["check_steps"]
        self.seen += 1
        if len(self.kept) < n:
            self.kept.append((self.t - 1, k, v))
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < n:
                self.kept[j] = (self.t - 1, k, v)
                self.replaced.append(len(self.step_s) - 1)

    def end_to_end(self, units, window_s):
        return {"store_tenant_steps_per_s": units * self.B / window_s}

    def attempted(self) -> int:
        return self.seen * self.B

    def failed(self) -> int:
        return self._failed

    def layer_context(self, units):
        from repro.core import daemon_store
        led = daemon_store.ledger(self.state)
        delta = {k: led[k] - self.led0[k] for k in
                 ("local_hits", "evictions", "page_moves", "requests")}
        st = self.cfg["store"]
        return {"units": units, "ledger_delta": delta, "stores": self.S,
                "slots": st["local_pages_per_tenant"],
                "row_bytes": int(np.prod(self.row)) * 2}

    # ------------------------------------------------------------- check
    def _streams_run(self):
        idx = np.arange(self.t) % self.T
        return tuple(x[idx] for x in self.streams)

    def check(self, control=None):
        """The compared numbers, each with its limit. `control` = (rnd,
        dtype name) puts the reference in the program's place instead,
        its simulated times rounded by `rnd` and its pages passed through
        that dtype: the run that has to fail."""
        import jax
        import jax.numpy as jnp
        from repro.core import daemon_store

        if self._count_gc in gc.callbacks:
            gc.callbacks.remove(self._count_gc)
        self.rk = self.rv = None       # the checks read the tier by formula
        pages, offs, writes = self._streams_run()
        spec = store_ref.StoreSpec(self.cfg, self.S)
        ref = store_ref.run(spec, pages, offs, writes)
        row = self.row
        salts = jnp.asarray(np.asarray(self.salts, np.uint32))

        @jax.jit
        def rows_wrong(got_k, got_v, ids, salts):
            bad = jnp.zeros(ids.shape, bool)
            for w, got in enumerate((got_k, got_v)):
                want = remote_tier.rows(ids, row, salts[w], jnp)
                diff = (jax.lax.bitcast_convert_type(got, jnp.uint16)
                        != jax.lax.bitcast_convert_type(want, jnp.uint16))
                bad = bad | jnp.any(diff.reshape(ids.shape[0], -1), axis=1)
            return bad

        # the hit flags of the last `n` steps are still in the buffer
        n = min(self.t, self.T)
        last = np.arange(self.t - n, self.t) % self.T
        kept = sorted(self.kept, key=lambda x: x[0])
        if control is None:
            led = daemon_store.ledger(self.state)
            tables = np.asarray(self.state.seqs.res.page)
            hits = np.asarray(self.hit_buf)[last]
            flat = [p.reshape((-1,) + row) for p in
                    (self.state.seqs.kpool, self.state.seqs.vpool)]
            pool_rows = lambda a, b, ids: (flat[0][a:b], flat[1][a:b])
        else:
            rnd, low = control
            low = getattr(jnp, low)
            cref = store_ref.run(spec, pages, offs, writes, rnd=rnd)
            led = dict(cref["ledger"], module_bytes=cref["module_bytes"])
            tables, hits = cref["tables"], cref["hits"][-n:]

            def lowered(ids):
                return tuple(remote_tier.rows(ids, row, salts[w], jnp)
                             .astype(low).astype(jnp.bfloat16)
                             for w in (0, 1))
            kept = [(t,) + lowered(jnp.asarray(
                pages[t].reshape(-1).astype(np.uint32))) for t, _, _ in kept]
            pool_rows = lambda a, b, ids: lowered(ids)
        served = 0
        for t, k, v in kept:
            ids = jnp.asarray(pages[t].reshape(-1).astype(np.uint32))
            served += int(jnp.sum(rows_wrong(
                k.reshape((-1,) + row), v.reshape((-1,) + row), ids, salts)))
        live = tables.reshape(-1) >= 0
        slots = np.maximum(tables.reshape(-1), 0).astype(np.uint32)
        pool_wrong = 0
        for a in range(0, slots.shape[0], CHECK_BLOCK):
            b = min(a + CHECK_BLOCK, slots.shape[0])
            ids = jnp.asarray(slots[a:b])
            bad = np.asarray(rows_wrong(*pool_rows(a, b, ids), ids, salts))
            pool_wrong += int(np.sum(bad & live[a:b]))
        gaps = [abs(led[k] - ref["ledger"][k]) / max(ref["ledger"][k], 1.0)
                for k in store_ref.LEDGER_KEYS]
        gaps += [abs(a - b) / max(b, 1.0) for a, b in
                 zip(led["module_bytes"], ref["module_bytes"])]
        if control is None:
            self._failed = served
            self.state = self.hit_buf = None   # free the program's state
            self.summary = {k: led[k] for k in store_ref.LEDGER_KEYS}
            self.summary.update(self._host_steps())
        checks = {
            "served_rows_wrong": float(served),
            "pool_rows_wrong": float(pool_wrong),
            "hits_gap": float(np.mean(hits != ref["hits"][-n:])),
            "tables_gap": float(np.mean(tables != ref["tables"])),
            "ledger_gap": float(max(gaps)),
        }
        return [{"name": k, "value": v, "limit": LIMITS[k]}
                for k, v in checks.items()]

    def _host_steps(self) -> dict:
        """Host time of the window's steps: p50, p95, max, and the three
        slowest with their process CPU time, the collections run in them,
        whether the sample of served pages changed there, and the
        wall-clock time at their end."""
        if not self.step_s:
            return {}
        wall = np.asarray(self.step_s) * 1e3
        q = np.quantile(wall, [0.5, 0.95, 1.0])
        slow = [[int(i), round(float(wall[i]), 3),
                 round(self.step_cpu_s[i] * 1e3, 3), self.step_gcs[i],
                 int(i) in self.replaced, self.step_at[i]]
                for i in np.argsort(-wall)[:3]]
        return {"host_step_ms_p50_p95_max": q.round(3).tolist(),
                "slowest_steps_i_ms_cpu_ms_gcs_resampled_at": slow}

