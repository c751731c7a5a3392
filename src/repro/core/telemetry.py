"""Telemetry plane: traceable observability riding the compiled lattices.

The repo's metrics were scalar sums (desim's ``lat_sum``/``n``, the
store's byte ledger) — enough for mean access cost, blind to the tail
the paper's critical-path argument is actually about. This module adds
two *traced-data* instruments, carried through ``lax.scan`` like
``SchemeFlags``/``PolicyFlags`` are, plus the static config axis that
gates them:

- a fixed-bin **log-spaced latency histogram** (``record_latency``):
  per-cell scatter-adds into a (BINS,) count vector, from which exact
  in-lattice percentiles (p50/p95/p99) are read by a CDF walk over the
  bins (``percentiles_from_state`` / ``approx_percentiles``). The
  estimator matches ``numpy.percentile(method="inverted_cdf")`` up to
  one bin width (pinned by a hypothesis test): the selected bin is the
  one holding the smallest sample whose CDF reaches q, and the reported
  value is the bin's geometric midpoint.
- a fixed-capacity **per-step time-series ring** (``record_series``):
  one (CAP, C) float row every ``series_every`` steps (channel backlog,
  adaptive ratio, hit rate, evictions, writeback bytes, module health —
  the channel set is the caller's), overwriting oldest-first so the
  memory cost is static regardless of run length. ``series_rows``
  unwraps the ring host-side into time order for the exporter
  (``repro.runtime.obs``).

Gating mirrors the ``kernel_impl`` lattice (DESIGN.md §9/§10): the
STATIC ``TelemetryConfig.level`` axis — ``off`` < ``counters`` <
``histogram`` — decides at trace time which instruments exist. ``off``
yields ``init_state(...) is None``: ``None`` is a leafless pytree, so a
``tel=None`` field on ``SimState``/``SeqState`` adds ZERO ops and ZERO
leaves to the compiled program — bit-identity with the pre-telemetry
outputs and unchanged compile counts are structural, not best-effort
(pinned by goldens + compile-count tests). ``counters`` turns on the
series ring, ``histogram`` adds the latency histogram. Host spans are no
level: a loop records them when its caller passes a
``repro.runtime.obs.SpanRecorder``.

The bin EDGES ride inside ``TelemetryState`` as a constant (BINS+1,)
leaf rather than being recomputed from config at every consumer: state
in hand is enough to read percentiles (``ledger`` has no config), and a
constant leaf through scan costs nothing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# the level lattice, in order: each level includes everything below it
LEVELS = ("off", "counters", "histogram")


@dataclass(frozen=True)
class TelemetryConfig:
    """STATIC observability axis (hashable — rides jit static args like
    `KVStoreConfig`/`SimConfig` do). `lat_lo`/`lat_hi` bound the
    histogram's log-spaced bin range in the caller's latency unit
    (nanoseconds on desim, decode steps on the store); values below
    `lat_lo` clamp into bin 0, above `lat_hi` into the last bin."""
    level: str = "off"
    bins: int = 64                # histogram bins (log-spaced)
    lat_lo: float = 1.0           # lower edge of bin 0 (> 0)
    lat_hi: float = 1e8           # upper edge of the last bin
    series_cap: int = 128         # ring capacity (rows kept)
    series_every: int = 1         # sample every k steps

    def __post_init__(self):
        if self.level not in LEVELS:
            raise ValueError(f"level must be one of {LEVELS}, "
                             f"got {self.level!r}")
        if self.bins < 2:
            raise ValueError(f"bins must be >= 2, got {self.bins}")
        if not (0.0 < self.lat_lo < self.lat_hi):
            raise ValueError(f"need 0 < lat_lo < lat_hi, got "
                             f"({self.lat_lo}, {self.lat_hi})")
        if self.series_cap < 1 or self.series_every < 1:
            raise ValueError("series_cap and series_every must be >= 1")

    @property
    def enabled(self) -> bool:
        return self.level != "off"

    @property
    def series_on(self) -> bool:
        return self.level in ("counters", "histogram")

    @property
    def histogram_on(self) -> bool:
        return self.level == "histogram"


class TelemetryState(NamedTuple):
    """Traced instrument state — a pytree of jnp leaves that rides the
    scan carry (desim) or the per-sequence batch axis (store)."""
    hist: jnp.ndarray       # (BINS,) f32 latency counts
    edges: jnp.ndarray      # (BINS+1,) f32 log-spaced bin edges (constant)
    series: jnp.ndarray     # (CAP, C) f32 ring of sampled channel rows
    series_n: jnp.ndarray   # () f32 samples taken (ring write cursor)


def bin_edges(cfg: TelemetryConfig) -> np.ndarray:
    """(BINS+1,) log-spaced edges over [lat_lo, lat_hi] (host-side)."""
    return np.logspace(np.log10(cfg.lat_lo), np.log10(cfg.lat_hi),
                       cfg.bins + 1).astype(np.float32)


def init_state(cfg: Optional[TelemetryConfig],
               channels: int) -> Optional[TelemetryState]:
    """Fresh instrument state, or None when telemetry is off — None is
    pytree-transparent, so the off level adds no leaves to compiled
    programs (the bit-identity/compile-count guarantee)."""
    if cfg is None or not cfg.enabled:
        return None
    return TelemetryState(
        hist=jnp.zeros((cfg.bins,), F32),
        edges=jnp.asarray(bin_edges(cfg)),
        series=jnp.zeros((cfg.series_cap, channels), F32),
        series_n=jnp.zeros((), F32),
    )


def record_latency(tel: Optional[TelemetryState], cfg: TelemetryConfig,
                   value, gate=True) -> Optional[TelemetryState]:
    """Scatter `value` (scalar or vector, the caller's latency unit) into
    the log-spaced histogram. `gate` (bool, broadcastable to `value`)
    drops masked samples via an out-of-bounds scatter index — the warm
    gating / miss gating hook. No-op below the histogram level."""
    if tel is None or not cfg.histogram_on:
        return tel
    v = jnp.asarray(value, F32).reshape(-1)
    g = jnp.broadcast_to(jnp.asarray(gate, bool), v.shape)
    span = np.log(cfg.lat_hi / cfg.lat_lo)
    idx = jnp.floor(jnp.log(jnp.maximum(v, 1e-30) / cfg.lat_lo)
                    / span * cfg.bins).astype(jnp.int32)
    idx = jnp.clip(idx, 0, cfg.bins - 1)
    hist = tel.hist.at[jnp.where(g, idx, cfg.bins)].add(1.0, mode="drop")
    return tel._replace(hist=hist)


def record_series(tel: Optional[TelemetryState], cfg: TelemetryConfig,
                  step, values) -> Optional[TelemetryState]:
    """Write one (C,) channel row into the ring when `step` (0-based) is
    on the `series_every` grid; off-grid steps scatter out of bounds and
    drop. The ring index wraps, so a long run keeps the LAST `series_cap`
    samples. No-op below the counters level."""
    if tel is None or not cfg.series_on:
        return tel
    step = jnp.asarray(step, jnp.int32)
    on_grid = (step % cfg.series_every) == 0
    row = jnp.where(on_grid, (step // cfg.series_every) % cfg.series_cap,
                    cfg.series_cap)
    series = tel.series.at[row].set(jnp.asarray(values, F32), mode="drop")
    return tel._replace(series=series,
                        series_n=tel.series_n + jnp.where(on_grid, 1.0,
                                                          0.0))


def merge(a: Optional[TelemetryState],
          b: Optional[TelemetryState]) -> Optional[TelemetryState]:
    """Histogram-sum two states (batch fold); series keeps `a`'s ring."""
    if a is None or b is None:
        return a if b is None else b
    return a._replace(hist=a.hist + b.hist)


# --------------------------------------------------------------- readers
def approx_percentiles(hist, edges, qs):
    """In-lattice percentile read: for each q in `qs` (fractions in
    (0, 1]), the geometric midpoint of the bin holding the smallest
    sample whose CDF reaches q — `numpy.percentile(method=
    "inverted_cdf")` up to one bin width. jnp-traceable (works under
    vmap across lattice cells); returns 0 for an empty histogram."""
    hist = jnp.asarray(hist, F32)
    edges = jnp.asarray(edges, F32)
    mids = jnp.sqrt(edges[:-1] * edges[1:])
    total = jnp.sum(hist)
    cum = jnp.cumsum(hist)
    qs_arr = jnp.asarray(qs, F32).reshape(-1)
    idx = jnp.argmax(cum[None, :] >= qs_arr[:, None] * total, axis=1)
    return jnp.where(total > 0, mids[idx], 0.0)


def percentiles_from_state(tel: TelemetryState, qs,
                           base: Optional[TelemetryState] = None) -> list:
    """Host-side percentile read from a (possibly batched) state. A
    leading batch axis on `hist` is summed — the store's per-tenant
    histograms aggregate to one service-lag distribution. `base`
    (optional warm-boundary snapshot) is subtracted first, the same
    delta gating the benchmarks apply to scalar stats."""
    hist = np.asarray(tel.hist, np.float64)
    if base is not None:
        hist = hist - np.asarray(base.hist, np.float64)
    hist = hist.reshape(-1, hist.shape[-1]).sum(axis=0)
    edges = np.asarray(tel.edges, np.float64).reshape(-1)[
        : hist.shape[0] + 1]
    mids = np.sqrt(edges[:-1] * edges[1:])
    total = hist.sum()
    if total <= 0:
        return [0.0 for _ in np.atleast_1d(qs)]
    cum = np.cumsum(hist)
    return [float(mids[int(np.argmax(cum >= q * total))])
            for q in np.atleast_1d(qs)]


def series_rows(tel: TelemetryState, cfg: TelemetryConfig):
    """Unwrap the ring into time order (host-side). Returns
    (steps (n,) int64, rows (n, C) float32) — the sampled step index of
    each kept row and its channel values, oldest first."""
    series = np.asarray(tel.series)
    n = int(np.asarray(tel.series_n))
    cap = series.shape[0]
    if n <= cap:
        rows = series[:n]
        first = 0
    else:
        cut = n % cap
        rows = np.concatenate([series[cut:], series[:cut]], axis=0)
        first = n - cap
    steps = (first + np.arange(rows.shape[0], dtype=np.int64)) \
        * cfg.series_every
    return steps, rows.astype(np.float32)
