"""Serving loop: batched autoregressive decode with greedy/temperature
sampling, optional DaeMon paged-KV movement accounting.

`serve_batch` drives `decode_step` (prefill via teacher-forced forward on
the prompt, then token-by-token with the layer-stacked cache). This is the
entry the `decode_*` dry-run cells lower; examples/serve_paged.py runs it
on a reduced config and reports the DaemonKVStore byte ledger.

`serve_batch_paged` is the disaggregated-KV variant: the same decode loop
with the batched two-tier DaemonKVStore in it — B tenant sequences, each
with its own local page pool and engine, contending for ONE movement
fabric spanning M memory modules (`repro.core.daemon_store` /
`repro.core.fabric`). Each decode step requests every sequence's hot KV
pages (real token offsets, so sub-block keys dedup like the simulator's
packed page*lines_per_page+off keys) and the ledger records the wire
traffic the decode costs on a disaggregated KV tier. The fabric's link
may be a time-varying `LinkModel` (per-module bandwidth schedule +
health masks); a `runtime.fault.LinkHealthMonitor` watching the sampled
health surfaces reshard advisories for degraded/flapping modules in the
returned ledger.

`serve_replicated` is the compute-plane variant: C serving replicas x B
tenants each against ONE memory-side fabric, every replica's transfers
additionally serialized on its own NIC bank (two-leg pricing,
`repro.core.compute_plane`) — the serving analogue of the paper's
multiple-compute-components scaling axis (fig 22), and what
`benchmarks/scaling.py` sweeps into BENCH_scale.json.

All three loops serve the store's residency transaction through the
fused kernel path by default — `KVStoreConfig.kernel_impl` (DESIGN.md
§9) rides in on the `store_cfg` the caller passes, so pinning
`kernel_impl="ref"`/`"chain"` here needs no loop changes.
"""
from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.daemon_store import (KVStoreConfig, init_kv_store_batch,
                                     init_kv_store_replicated,
                                     ledger as store_ledger,
                                     step_fetch_batch,
                                     step_fetch_replicated)
from repro.models.model import (ModelOptions, decode_step,
                                init_decode_state)


@dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 => greedy
    seed: int = 0


@dataclass(frozen=True)
class PagedServeConfig:
    """Paged-KV movement accounting knobs for `serve_batch_paged`."""
    window_pages: int = 4     # hot KV pages requested per sequence per step
    pages_per_seq: int = 32   # remote-tier pages reserved per tenant


def _span(rec, name, **args):
    """`rec.span(...)` or a no-op context yielding a writable dict. The
    loops record spans only into a `repro.runtime.obs.SpanRecorder` the
    caller passes; a span blocks on the phase's outputs, so recording
    serializes the dispatch pipeline."""
    return nullcontext({}) if rec is None else rec.span(name, **args)


def make_decode_fn(cfg: ArchConfig, opt: ModelOptions):
    @jax.jit
    def step(params, state, tokens, pos, key, temperature):
        logits, state = decode_step(params, cfg, state, tokens, pos, opt)
        logits = logits[:, : cfg.vocab_size]
        greedy = jnp.argmax(logits, axis=-1)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-4), axis=-1)
        nxt = jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
        return nxt[:, None], state
    return step


def serve_batch(params, cfg: ArchConfig, prompts, scfg: ServeConfig,
                opt: ModelOptions = None, recorder=None):
    """prompts: (B, P) int32. Returns (B, P + max_new_tokens) tokens.

    Prefill is run token-by-token through the same decode cell (exact, and
    exercises every recurrent family uniformly); production prefill for
    attention archs uses models.model.prefill (one pass) — both paths are
    tested for equivalence. `recorder` (optional
    `repro.runtime.obs.SpanRecorder`) captures prefill/decode spans for
    the Perfetto export.
    """
    opt = opt or ModelOptions(remat="none")
    b, p = prompts.shape
    max_len = p + scfg.max_new_tokens
    state, _ = init_decode_state(cfg, b, max_len, opt)
    step = make_decode_fn(cfg, opt)
    key = jax.random.PRNGKey(scfg.seed)
    out = [prompts]
    # zero-length prompts skip prefill and decode from a BOS-like token 0
    nxt = jnp.zeros((b, 1), jnp.int32)
    with _span(recorder, "prefill", tokens=p) as sp:
        for i in range(p):
            key, sub = jax.random.split(key)
            nxt, state = step(params, state, prompts[:, i:i + 1],
                              jnp.int32(i), sub,
                              jnp.float32(scfg.temperature))
        sp["sync"] = nxt
    tok = nxt
    gen = []
    with _span(recorder, "decode", tokens=scfg.max_new_tokens) as sp:
        for i in range(scfg.max_new_tokens):
            gen.append(tok)
            key, sub = jax.random.split(key)
            tok, state = step(params, state, tok, jnp.int32(p + i), sub,
                              jnp.float32(scfg.temperature))
        sp["sync"] = tok
    return jnp.concatenate(out + gen, axis=1)


def paged_request_window(positions, seq_ids, page_tokens: int,
                         window: int, pages_per_seq: int):
    """Per-sequence hot-page window at the given decode positions.

    Returns (pages (B, W) int32, offsets (B, W) int32, writes (B, W)
    bool): the W most recently written KV pages of each sequence, mapped
    into the tenant's region of the shared remote pool
    (`seq * pages_per_seq + logical`), with the request's real token
    offset within its page — the current position's offset on the newest
    page, the page's last token on the older (fully written) ones. The
    newest page (j == 0) is the one the current position APPENDS KV to:
    its `writes` flag is set, so the store marks the resident copy dirty
    and its eventual eviction pays a writeback (§4.3 serving side).
    """
    positions = jnp.asarray(positions, jnp.int32)
    seq_ids = jnp.asarray(seq_ids, jnp.int32)
    cur = jnp.minimum(positions // page_tokens, pages_per_seq - 1)  # (B,)
    j = jnp.arange(window, dtype=jnp.int32)                # (W,)
    logical = jnp.maximum(cur[:, None] - j[None, :], 0)
    pages = seq_ids[:, None] * pages_per_seq + logical
    offs = jnp.where(j[None, :] == 0,
                     positions[:, None] % page_tokens,
                     page_tokens - 1)
    writes = jnp.broadcast_to(j[None, :] == 0, pages.shape)
    return pages.astype(jnp.int32), offs.astype(jnp.int32), writes


def serve_batch_paged(params, cfg: ArchConfig, prompts, scfg: ServeConfig,
                      store_cfg: KVStoreConfig,
                      pcfg: PagedServeConfig = PagedServeConfig(),
                      opt: ModelOptions = None, link=None,
                      health_monitor=None, recorder=None):
    """Batched decode with the DaeMon movement plane in the loop.

    Runs the same prefill + decode schedule as `serve_batch`, and per
    step drives the batched two-tier store with each sequence's hot-page
    window: B tenants (own pool/page-table/engine) share one fabric whose
    per-module channels their page migrations queue on. The decode
    computes from its dense cache; the store is the movement plane of the
    disaggregated KV tier, and its ledger is the cost report.

    `link` (optional `fabric.LinkModel`, knot times in decode steps)
    makes the fabric's per-module bandwidth/health time-varying;
    `health_monitor` (optional `runtime.fault.LinkHealthMonitor`) then
    watches the sampled per-module health each decode step — the ledger
    gains `link_reshard_modules`, the modules for which a reshard was
    advised mid-run (a degraded module should shed its pages, the
    serving analogue of `StragglerDetector.should_reshard`).

    `recorder` (optional `repro.runtime.obs.SpanRecorder`) captures
    prefill/decode-step spans into its `events` (ready for
    `obs.trace_export`). The store's own
    histogram/series telemetry rides in on `store_cfg.telemetry` like
    `kernel_impl` does — ledger percentile columns need no loop changes.

    Returns (tokens (B, P + max_new_tokens), ledger dict).
    """
    opt = opt or ModelOptions(remat="none")
    b, p = prompts.shape
    max_len = p + scfg.max_new_tokens
    state, _ = init_decode_state(cfg, b, max_len, opt)
    step = make_decode_fn(cfg, opt)
    key = jax.random.PRNGKey(scfg.seed)

    kv = init_kv_store_batch(store_cfg, b, link=link)
    reshard_advised = set()
    if health_monitor is not None and link is not None:
        # snapshot the (host-known, constant) schedule once: per-step
        # sampling is then a numpy searchsorted, not a device round-trip
        # in the decode hot loop
        sched_t = jax.device_get(link.sched_t)
        sched_health = jax.device_get(link.health)

    def watch_health(clock_step: int):
        if health_monitor is None or link is None:
            return
        seg = np.clip(np.searchsorted(sched_t, clock_step, side="right")
                      - 1, 0, len(sched_t) - 1)
        reshard_advised.update(health_monitor.observe(sched_health[seg]))
    n_remote = b * pcfg.pages_per_seq
    rshape = (n_remote, store_cfg.page_tokens, store_cfg.kv_heads,
              store_cfg.head_dim)
    remote_k = jnp.zeros(rshape, jnp.bfloat16)
    remote_v = jnp.zeros(rshape, jnp.bfloat16)
    seq_ids = jnp.arange(b, dtype=jnp.int32)

    @jax.jit
    def kv_step(kv_state, pos):
        need, offs, writes = paged_request_window(
            jnp.full((b,), pos, jnp.int32), seq_ids,
            store_cfg.page_tokens, pcfg.window_pages, pcfg.pages_per_seq)
        kv_state, _, _, _ = step_fetch_batch(kv_state, store_cfg,
                                             remote_k, remote_v, need,
                                             offs, writes)
        return kv_state

    out = [prompts]
    # zero-length prompts skip prefill and decode from a BOS-like token 0
    nxt = jnp.zeros((b, 1), jnp.int32)
    with _span(recorder, "prefill", tokens=p) as sp:
        for i in range(p):
            key, sub = jax.random.split(key)
            nxt, state = step(params, state, prompts[:, i:i + 1],
                              jnp.int32(i), sub,
                              jnp.float32(scfg.temperature))
            kv = kv_step(kv, jnp.int32(i))
            watch_health(i + 1)
        sp["sync"] = (nxt, kv.fab.page_busy)
    tok = nxt
    gen = []
    with _span(recorder, "decode", tokens=scfg.max_new_tokens) as sp:
        for i in range(scfg.max_new_tokens):
            gen.append(tok)
            key, sub = jax.random.split(key)
            with _span(recorder, "decode_step", tid=1, step=i) as s2:
                tok, state = step(params, state, tok, jnp.int32(p + i),
                                  sub, jnp.float32(scfg.temperature))
                kv = kv_step(kv, jnp.int32(p + i))
                s2["sync"] = (tok, kv.fab.page_busy)
            watch_health(p + i + 1)
        sp["sync"] = tok
    led = store_ledger(kv)
    if health_monitor is not None:
        led["link_reshard_modules"] = sorted(reshard_advised)
    if kv.seqs.tel is not None:
        # raw per-tenant telemetry state (jnp pytree, NOT json) for the
        # examples' obs export; json writers must pop it first
        led["_tel"] = kv.seqs.tel
    return jnp.concatenate(out + gen, axis=1), led


def serve_replicated(params, cfg: ArchConfig, prompts, scfg: ServeConfig,
                     store_cfg: KVStoreConfig, num_replicas: int,
                     pcfg: PagedServeConfig = PagedServeConfig(),
                     opt: ModelOptions = None, link=None, recorder=None,
                     mesh=None):
    """Replicated serving: C serving replicas x B tenants each, one
    shared memory-side fabric (the compute plane, DESIGN.md §7).

    Runs the `serve_batch_paged` decode schedule over the C*B flattened
    sequence set (each replica decodes its own B-tenant batch of the
    given prompts) and per step drives `step_fetch_replicated`: every
    replica's page migrations queue on the SAME per-module memory
    channels while additionally serializing on the replica's own NIC
    bank — the multi-client-contention workload of a real disaggregated
    rack. Each of the C*B tenants owns a distinct region of one shared
    remote KV pool.

    `mesh` (optional 1-axis ``("data",)`` device mesh, see
    `repro.runtime.mesh_plane` / DESIGN.md §11) places the replica axis
    on real devices: per-replica state and NICs device-local, the shared
    module bank psum-merged at the fabric boundary each step. C must
    divide evenly across the mesh; a 1-device mesh is bit-identical to
    the default vmap path.

    Returns (tokens (C, B, P + max_new_tokens), ledger dict — including
    per-module `module_bytes` and per-replica `unit_bytes`).
    """
    opt = opt or ModelOptions(remat="none")
    c = num_replicas
    b, p = prompts.shape
    flat_prompts = jnp.tile(prompts, (c, 1))             # (C*B, P)
    max_len = p + scfg.max_new_tokens
    state, _ = init_decode_state(cfg, c * b, max_len, opt)
    step = make_decode_fn(cfg, opt)
    key = jax.random.PRNGKey(scfg.seed)

    kv = init_kv_store_replicated(store_cfg, c, b, link=link)
    n_remote = c * b * pcfg.pages_per_seq
    rshape = (n_remote, store_cfg.page_tokens, store_cfg.kv_heads,
              store_cfg.head_dim)
    remote_k = jnp.zeros(rshape, jnp.bfloat16)
    remote_v = jnp.zeros(rshape, jnp.bfloat16)
    seq_ids = jnp.arange(c * b, dtype=jnp.int32)

    if mesh is not None:
        from repro.runtime import mesh_plane
        kv = mesh_plane.shard_replicated_state(kv, mesh)

    @jax.jit
    def request_window(pos):
        need, offs, writes = paged_request_window(
            jnp.full((c * b,), pos, jnp.int32), seq_ids,
            store_cfg.page_tokens, pcfg.window_pages, pcfg.pages_per_seq)
        shape = (c, b, pcfg.window_pages)
        return (need.reshape(shape), offs.reshape(shape),
                writes.reshape(shape))

    @jax.jit
    def kv_step_vmap(kv_state, pos):
        need, offs, writes = request_window(pos)
        kv_state, _, _, _ = step_fetch_replicated(
            kv_state, store_cfg, remote_k, remote_v, need, offs, writes)
        return kv_state

    def kv_step_sharded(kv_state, pos):
        need, offs, writes = request_window(pos)
        kv_state, _, _, _ = mesh_plane.step_replicated_sharded(
            kv_state, store_cfg, mesh, remote_k, remote_v, need, offs,
            writes)
        return kv_state

    kv_step = kv_step_vmap if mesh is None else kv_step_sharded

    out = [flat_prompts]
    # zero-length prompts skip prefill and decode from a BOS-like token 0
    nxt = jnp.zeros((c * b, 1), jnp.int32)
    with _span(recorder, "prefill", tokens=p) as sp:
        for i in range(p):
            key, sub = jax.random.split(key)
            nxt, state = step(params, state, flat_prompts[:, i:i + 1],
                              jnp.int32(i), sub,
                              jnp.float32(scfg.temperature))
            kv = kv_step(kv, jnp.int32(i))
        sp["sync"] = (nxt, kv.fab.page_busy)
    tok = nxt
    gen = []
    with _span(recorder, "decode", tokens=scfg.max_new_tokens) as sp:
        for i in range(scfg.max_new_tokens):
            gen.append(tok)
            key, sub = jax.random.split(key)
            tok, state = step(params, state, tok, jnp.int32(p + i), sub,
                              jnp.float32(scfg.temperature))
            kv = kv_step(kv, jnp.int32(p + i))
        sp["sync"] = (tok, kv.fab.page_busy)
    tokens = jnp.concatenate(out + gen, axis=1)
    led = store_ledger(kv)
    if kv.seqs.tel is not None:
        led["_tel"] = kv.seqs.tel
    return tokens.reshape((c, b, -1)), led
