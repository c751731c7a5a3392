"""Host-side telemetry export: serve-loop spans + captured series out to
Chrome trace-event JSON (Perfetto-loadable) and a text summary.

The traced half of the observability plane lives in
``repro.core.telemetry`` (histograms + series rings carried as data
through the compiled programs); this module is the untraced half — what
runs on the host around the jitted steps:

- ``SpanRecorder``: wall-clock "X" (complete) span events around host
  loop phases (prefill / decode / kv steps), recorded only when the
  caller passes a recorder. Recording a span blocks on its outputs (the
  caller passes them to ``span(..., sync=...)``), so span durations are
  real compute, not async dispatch time. Each span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so under a profiler
  trace it lands in the ``.xplane.pb`` on the device trace's clock,
  beside the device ops it dispatched.
- ``trace_export``: assembles spans + telemetry series into one Chrome
  trace-event JSON document (``{"traceEvents": [...]}`` with "X" spans
  and "C" counter tracks) that drags straight into https://ui.perfetto.
  dev. Counter rows come from ``telemetry.series_rows`` and are placed
  on a synthetic steps-as-microseconds timebase when no wall clock is
  attached (the series is sampled at the decode-step clock, which has
  no wall time inside a compiled scan).
- ``summary``: the examples' text block — percentiles + last series
  sample per channel.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Optional

import jax
import numpy as np

from repro.core.telemetry import (TelemetryConfig, TelemetryState,
                                  percentiles_from_state, series_rows)


class SpanRecorder:
    """Collects Chrome trace "X" (complete) events on a host wall clock
    relative to construction time. `span(...)` optionally blocks on a
    pytree of outputs before closing, so the recorded duration covers
    the device work the phase dispatched; the same interval is a
    `jax.profiler.TraceAnnotation` for a profiler trace, if one runs."""

    def __init__(self, pid: int = 0):
        self.pid = pid
        self.events: list = []
        self._t0 = time.perf_counter()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, tid: int = 0, **args):
        t_start = self._now_us()
        sync = {}
        with jax.profiler.TraceAnnotation(name):
            try:
                yield sync
            finally:
                if "sync" in sync and sync["sync"] is not None:
                    jax.block_until_ready(sync["sync"])
                self.events.append({
                    "name": name, "ph": "X", "ts": t_start,
                    "dur": self._now_us() - t_start,
                    "pid": self.pid, "tid": tid,
                    "args": {k: _jsonable(v) for k, v in args.items()},
                })

    def instant(self, name: str, tid: int = 0, **args):
        self.events.append({
            "name": name, "ph": "i", "ts": self._now_us(), "s": "t",
            "pid": self.pid, "tid": tid,
            "args": {k: _jsonable(v) for k, v in args.items()},
        })


def _jsonable(v):
    if isinstance(v, (np.generic, np.ndarray)):
        return v.tolist()
    if hasattr(v, "item") and getattr(v, "ndim", None) == 0:
        return v.item()
    return v


def counter_events(tel: TelemetryState, cfg: TelemetryConfig, labels,
                   *, pid: int = 0, name_prefix: str = "",
                   step_us: float = 1000.0, t0_us: float = 0.0) -> list:
    """Telemetry series ring -> one Chrome "C" counter track per channel
    label. The timebase is synthetic — `step_us` microseconds per decode
    step (the series is sampled at the compiled clock, which carries no
    wall time) — offset by `t0_us` so counters can be laid under real
    spans."""
    steps, rows = series_rows(tel, cfg)
    if rows.shape[1] != len(labels):
        raise ValueError(f"series has {rows.shape[1]} channels but "
                         f"{len(labels)} labels given")
    events = []
    for j, label in enumerate(labels):
        name = f"{name_prefix}{label}"
        for s, row in zip(steps, rows):
            events.append({"name": name, "ph": "C",
                           "ts": t0_us + float(s) * step_us,
                           "pid": pid,
                           "args": {label: float(row[j])}})
    return events


def trace_export(path: Optional[str] = None, *, spans=None,
                 counters=None, metadata=None) -> dict:
    """Assemble spans (SpanRecorder.events) + counter events
    (`counter_events`) into one Chrome trace-event JSON document and
    optionally write it to `path`. Returns the document dict."""
    events = []
    for name, pid in (metadata or {}).items():
        events.append({"name": "process_name", "ph": "M", "ts": 0.0,
                       "pid": pid, "tid": 0, "args": {"name": name}})
    events.extend(spans or [])
    events.extend(counters or [])
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc


def summary(title: str, tel: TelemetryState, cfg: TelemetryConfig,
            labels, *, unit: str = "steps",
            warm: Optional[TelemetryState] = None) -> str:
    """Text telemetry block for the examples: tail percentiles (warm-
    delta when a warm snapshot is given) + the last sampled series row."""
    lines = [f"# telemetry: {title} (level={cfg.level})"]
    if cfg.histogram_on:
        p50, p95, p99 = percentiles_from_state(tel, [0.5, 0.95, 0.99],
                                               base=warm)
        lines.append(f"  latency {unit}: p50={p50:.3g} p95={p95:.3g} "
                     f"p99={p99:.3g}")
    if cfg.series_on:
        # a batched state carries per-tenant rings; summarize tenant 0
        t0 = (jax.tree.map(lambda x: x[0], tel)
              if tel.series.ndim == 3 else tel)
        steps, rows = series_rows(t0, cfg)
        if len(steps):
            last = rows[-1]
            pairs = " ".join(f"{k}={v:.4g}" for k, v in zip(labels, last))
            lines.append(f"  series[{len(steps)} samples, last @step "
                         f"{int(steps[-1])}]: {pairs}")
    return "\n".join(lines)
