"""Entry: the batched two-tier KV store holding MLA latent pages.

The store of `store_batch`, stepped the same way (`daemon_store.
step_fetch_batch`, one unit = one decode step of every tenant, each layer
of each tenant a store of its own), with the page layout of multi-head
latent attention: one tensor a page, `kv_lora_rank + qk_rope_head_dim`
bfloat16 values a token (`KVStoreConfig.latent_dim`), held as whole
(sublane, 128-lane) tiles (`KVStoreConfig.page_row`). The remote tier is
that one tensor, passed as `remote_k` with `remote_v=None`; the store
serves no v. The configuration states the model's numbers at the top
level, as the published config has them.

The check is `store_batch`'s, for one tensor: the plain reference
(`bench/reference/store_latent_ref.py`) replays every step that ran and
must agree on each step's hit flags, the final page tables and the
integer ledger with the per-module wire bytes; every served row of the
`check_steps` sampled steps, and every resident pool slot, must equal the
tier's row by formula.
"""
from __future__ import annotations

import gc
import time
from pathlib import Path

import numpy as np

import harness
from lowered import bf16_round
from reference import store_latent_ref
import remote_tier
import traffic

store_batch = harness.load_module(Path(__file__).with_name("store_batch.py"),
                                  "bench_entry_store_batch")

# Every number is an exact comparison (limit 0), as in store_batch.
LIMITS = dict(store_batch.LIMITS)
# the control: the reference with its simulated times in bfloat16 and its
# pages passed through float8 e4m3
CONTROL = (bf16_round, "float8_e4m3fn")
CHECK_BLOCK = store_batch.CHECK_BLOCK
# the tier's tensor index in `remote_tier` (the one latent tensor)
LATENT = 0


def store_config(cfg):
    from repro.core.daemon_store import KVStoreConfig
    from repro.core.fabric import FabricConfig
    from repro.core.params import DaemonParams
    st = cfg["store"]
    pages_per_tenant = st["context_tokens"] // st["page_tokens"]
    return KVStoreConfig(
        num_local_pages=st["local_pages_per_tenant"],
        page_tokens=st["page_tokens"],
        latent_dim=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
        daemon=DaemonParams(**cfg["daemon"]),
        compress_pages=st["compress_pages"],
        page_budget_per_step=st["page_budget_per_step"],
        selection=st["selection"], adaptive_ratio=st["adaptive_ratio"],
        fabric=FabricConfig(num_modules=st["memory_modules"],
                            placement=st["placement"],
                            affinity_block=pages_per_tenant),
        policy=st["policy"], pool_ways=st["pool_ways"], kernel_impl="auto")


class Cell(store_batch.Cell):
    def __init__(self, cfg, mix, seed, devs, scale):
        # the configuration is read (and the program's store built) first,
        # so that a program without the latent layout stops here
        self.cfg = dict(cfg, **scale.get("model", {}),
                        store=dict(cfg["store"], **scale.get("store", {})))
        self.kcfg = store_config(self.cfg)
        self.mix = dict(mix, **scale.get("mix", {}))
        self.seed, self.devs = seed, devs
        st = self.cfg["store"]
        self.B = self.mix["tenants"]
        self.layers = self.cfg["num_hidden_layers"]
        self.S = self.B * self.layers          # stores
        self.pages_per_tenant = st["context_tokens"] // st["page_tokens"]
        self.row = self.kcfg.page_row()
        self.trace_units = self.mix["trace_units"]
        self.rng = np.random.default_rng([seed, 2])
        self.salt = remote_tier.seed_words(seed, LATENT)
        self._failed = 0

    # ------------------------------------------------------------ set-up
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core import daemon_store

        t0 = time.perf_counter()
        dev = self.devs[0]
        kcfg = self.kcfg
        pages, offs, writes = traffic.store_streams(
            self.mix, self.pages_per_tenant, self.cfg["store"]["page_tokens"],
            self.seed, self.layers)
        self.streams = (pages, offs, writes)
        self.T = pages.shape[0]
        n_remote = self.S * self.pages_per_tenant
        row = self.row

        @jax.jit
        def make_tier(salt):
            ids = jnp.arange(n_remote, dtype=jnp.uint32)
            return remote_tier.rows(ids, row, salt, jnp)

        self.tier = make_tier(jax.device_put(np.uint32(self.salt), dev))
        self.dev_streams = jax.device_put((pages, offs, writes), dev)

        T = self.T

        def step(state, t, hits, tier, p, o, w):
            # the stream position rides on the device with the state, and
            # each step's hit flags land in a buffer there, so a step
            # costs the host one dispatch and one wait
            pick = lambda x: jax.lax.dynamic_index_in_dim(x, t,
                                                          keepdims=False)
            out, k, _, hit = daemon_store.step_fetch_batch(
                state, kcfg, tier, None, pick(p), pick(o), pick(w))
            hits = jax.lax.dynamic_update_index_in_dim(hits, hit, t, 0)
            return out, (t + 1) % T, hits, k

        state = jax.device_put(daemon_store.init_kv_store_batch(kcfg,
                                                                self.S), dev)
        pos = jax.device_put(np.int32(0), dev)
        hits = jax.device_put(np.zeros(pages.shape, bool), dev)
        t1 = time.perf_counter()
        self.compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            state, pos, hits, self.tier, *self.dev_streams).compile()
        t2 = time.perf_counter()
        self.state, self.pos, self.hit_buf = state, pos, hits
        self.t = 0
        self.step_s, self.step_cpu_s, self.step_gcs = [], [], []
        self.step_at = []
        self.gcs = 0
        gc.callbacks.append(self._count_gc)
        self.kept = []             # (step, rows, None) sampled in the window
        self.replaced = []         # steps at which the sample changed
        self.seen = 0
        for _ in range(self.mix["warm_steps"]):
            self._step()
        self.step_s, self.step_cpu_s, self.step_gcs = [], [], []
        self.step_at = []
        self.phases = {"inputs_s": t1 - t0, "compile_s": t2 - t1,
                       "warm_s": time.perf_counter() - t2}
        self.led0 = daemon_store.ledger(self.state)

    def _step(self):
        import jax
        from jax import profiler
        t0, c0, g0 = time.perf_counter(), time.process_time(), self.gcs
        with profiler.TraceAnnotation("bench.step.dispatch"):
            (self.state, self.pos, self.hit_buf, k) = self.compiled(
                self.state, self.pos, self.hit_buf, self.tier,
                *self.dev_streams)
        with profiler.TraceAnnotation("bench.step.wait"):
            jax.block_until_ready(k)
        self.step_s.append(time.perf_counter() - t0)
        self.step_cpu_s.append(time.process_time() - c0)
        self.step_gcs.append(self.gcs - g0)
        self.step_at.append(time.time())
        self.t += 1
        return k, None

    def layer_context(self, units):
        ctx = super().layer_context(units)
        from repro.core import daemon_store
        led = daemon_store.ledger(self.state)
        for k in ("landings", "remote_rows"):
            if k in led:
                ctx["ledger_delta"][k] = led[k] - self.led0[k]
        return ctx

    # ------------------------------------------------------------- check
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
        self.tier = None               # the checks read the tier by formula
        pages, offs, writes = self._streams_run()
        spec = store_latent_ref.StoreSpec(self.cfg, self.S)
        ref = store_latent_ref.run(spec, pages, offs, writes)
        row = self.row
        salt = jnp.uint32(self.salt)

        @jax.jit
        def rows_wrong(got, ids, salt):
            want = remote_tier.rows(ids, row, salt, jnp)
            diff = (jax.lax.bitcast_convert_type(got, jnp.uint16)
                    != jax.lax.bitcast_convert_type(want, jnp.uint16))
            return jnp.any(diff.reshape(ids.shape[0], -1), axis=1)

        # the hit flags of the last `n` steps are still in the buffer
        n = min(self.t, self.T)
        last = np.arange(self.t - n, self.t) % self.T
        kept = sorted(self.kept, key=lambda x: x[0])
        if control is None:
            led = daemon_store.ledger(self.state)
            tables = np.asarray(self.state.seqs.res.page)
            hits = np.asarray(self.hit_buf)[last]
            flat = self.state.seqs.kpool.reshape((-1,) + row)
            pool_rows = lambda a, b, ids: flat[a:b]
        else:
            rnd, low = control
            low = getattr(jnp, low)
            cref = store_latent_ref.run(spec, pages, offs, writes, rnd=rnd)
            led = dict(cref["ledger"], module_bytes=cref["module_bytes"])
            tables, hits = cref["tables"], cref["hits"][-n:]

            def lowered(ids):
                return (remote_tier.rows(ids, row, salt, jnp)
                        .astype(low).astype(jnp.bfloat16))
            kept = [(t, lowered(jnp.asarray(
                pages[t].reshape(-1).astype(np.uint32))), None)
                for t, _, _ in kept]
            pool_rows = lambda a, b, ids: lowered(ids)
        served = 0
        for t, k, _ in kept:
            ids = jnp.asarray(pages[t].reshape(-1).astype(np.uint32))
            served += int(jnp.sum(rows_wrong(k.reshape((-1,) + row), ids,
                                             salt)))
        live = tables.reshape(-1) >= 0
        slots = np.maximum(tables.reshape(-1), 0).astype(np.uint32)
        pool_wrong = 0
        for a in range(0, slots.shape[0], CHECK_BLOCK):
            b = min(a + CHECK_BLOCK, slots.shape[0])
            ids = jnp.asarray(slots[a:b])
            bad = np.asarray(rows_wrong(pool_rows(a, b, ids), ids, salt))
            pool_wrong += int(np.sum(bad & live[a:b]))
        gaps = [abs(led[k] - ref["ledger"][k]) / max(ref["ledger"][k], 1.0)
                for k in store_latent_ref.LEDGER_KEYS]
        gaps += [abs(a - b) / max(b, 1.0) for a, b in
                 zip(led["module_bytes"], ref["module_bytes"])]
        if control is None:
            self._failed = served
            self.state = self.hit_buf = None   # free the program's state
            self.summary = {k: led[k] for k in store_latent_ref.LEDGER_KEYS
                            + ("landings", "remote_rows",
                               "writeback_offers")}
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
