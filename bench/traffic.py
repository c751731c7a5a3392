"""The one traffic generator: a mix file's parameters + a seed -> requests.

Every mix under `bench/traffic/` is a JSON file of parameters; its `kind`
picks one of the generators below, all deterministic in the seed:

  llc_miss       LLC-miss traces of one workload of the configuration
                 (its Table 3 stream parameters), as the simulator
                 replays them;
  decode_window  per decode step, each tenant requests the newest pages
                 at its decode position and appends to the newest one;
                 positions advance one token a step and restart.

The store mixes return (T, S, R) arrays of page ids, token offsets and
write flags over S = tenants x layers stores (a tenant's layers are
stores side by side, each with its own region of the remote tier, and
all see the tenant's requests); the store wraps round them if a run
outlasts T steps.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple

import numpy as np


class Trace(NamedTuple):
    page: np.ndarray      # (R,) int32
    off: np.ndarray       # (R,) int32 in [0, 64)
    gap: np.ndarray       # (R,) float32 ns of compute before the miss
    wr: np.ndarray        # (R,) bool
    n_pages: int


def llc_miss_trace(w: dict, name: str, n_requests: int, seed: int
                   ) -> Trace:
    """LLC-miss trace of one workload: `streams` interleaved page visits
    of Poisson(`lines_per_visit`) consecutive lines, each visit's page
    sequential with probability `seq_frac` and otherwise zipf(`zipf`)
    over the footprint, exponential compute gaps, `dirty_frac` writes.
    A copy of the repo's calibrated generator (`sim/trace.py`), kept
    here so that the benchmark's traffic cannot move with the program."""
    rng = np.random.default_rng(seed * 9176
                                + zlib.crc32(name.encode()) % 65536)
    k = w["streams"]
    n_pages = w["n_pages"]
    pages = np.zeros(k, np.int64)
    remaining = np.zeros(k, np.int64)
    offsets = np.zeros(k, np.int64)
    seq_counter = rng.integers(0, n_pages)
    ranks = np.arange(1, n_pages + 1, dtype=np.float64)
    probs = ranks ** (-w["zipf"]) if w["zipf"] > 0 else np.ones_like(ranks)
    probs /= probs.sum()
    cdf = np.cumsum(probs)
    perm = rng.permutation(n_pages)
    page_out = np.zeros(n_requests, np.int32)
    off_out = np.zeros(n_requests, np.int32)
    pick = rng.integers(0, k, size=n_requests)
    useq = rng.random(n_requests)
    uz = rng.random(n_requests)
    burst = np.maximum(1, rng.poisson(w["lines_per_visit"],
                                      size=n_requests))
    for i in range(n_requests):
        s = pick[i]
        if remaining[s] <= 0:
            if useq[i] < w["seq_frac"]:
                seq_counter = (seq_counter + 1) % n_pages
                pages[s] = seq_counter
            else:
                pages[s] = perm[np.searchsorted(cdf, uz[i])]
            remaining[s] = min(64, burst[i])
            offsets[s] = rng.integers(0, 64)
        page_out[i] = pages[s]
        off_out[i] = offsets[s]
        offsets[s] = (offsets[s] + 1) % 64
        remaining[s] -= 1
    gap = rng.exponential(w["gap_ns"], size=n_requests).astype(np.float32)
    wr = rng.random(n_requests) < w["dirty_frac"]
    return Trace(page_out, off_out, gap, wr, n_pages)


def decode_window(mix: dict, pages_per_tenant: int, page_tokens: int,
                  seed: int):
    """(T, tenants, R): the R newest pages at each tenant's decode position
    (the newest appended to, the older ones read at their last token),
    positions advancing one token a step from seeded starts and
    restarting at a seeded position once the context is full."""
    rng = np.random.default_rng(seed)
    t, b, r = mix["stream_steps"], mix["tenants"], mix["requests_per_step"]
    ctx = pages_per_tenant * page_tokens
    lo = mix["start_min"]
    pos = rng.integers(lo, ctx, size=b)
    restarts = rng.integers(lo, ctx, size=(t, b))
    pages = np.zeros((t, b, r), np.int32)
    offs = np.zeros((t, b, r), np.int32)
    j = np.arange(r)
    for step in range(t):
        cur = pos // page_tokens
        logical = np.maximum(cur[:, None] - j[None, :], 0)
        pages[step] = np.arange(b)[:, None] * pages_per_tenant + logical
        offs[step] = np.where(j[None, :] == 0, (pos % page_tokens)[:, None],
                              page_tokens - 1)
        pos = pos + 1
        pos = np.where(pos >= ctx, restarts[step], pos)
    writes = np.broadcast_to(j[None, None, :] == 0, (t, b, r)).copy()
    return pages, offs, writes


def store_streams(mix: dict, pages_per_tenant: int, page_tokens: int,
                  seed: int, layers: int = 1):
    """(T, tenants x layers, R) page ids, offsets and write flags: each
    tenant's requests repeated for each of its layers' stores, page ids
    moved into that store's region (tenant-major)."""
    gen = {"decode_window": decode_window}
    pages, offs, writes = gen[mix["kind"]](mix, pages_per_tenant,
                                           page_tokens, seed)
    t, b, r = pages.shape
    logical = pages - (np.arange(b) * pages_per_tenant)[None, :, None]
    store = np.arange(b * layers).reshape(b, layers)
    pages = (logical[:, :, None, :]
             + (store * pages_per_tenant)[None, :, :, None])
    rep = lambda x: np.repeat(x, layers, axis=1)
    return (pages.reshape(t, b * layers, r).astype(np.int32), rep(offs),
            rep(writes))
