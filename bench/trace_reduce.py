"""From a profiler trace to the numbers the per-layer readers need.

`load` reads the `.xplane.pb` that `jax.profiler` wrote into a plain
dict, the form the readers and the test fixtures share:

    {"device": {<plane>: [[name, start_ns, dur_ns], ...]},
     "host":   [[name, start_ns, dur_ns], ...],
     "window": [start_ns, end_ns]}

`device` holds, per TPU, the operations of its "XLA Ops" line, named by
their HLO text (`%residency_fused.1 = ... custom-call(...)`), so that a
kernel is found by its stable name. `host` holds the benchmark's own
`bench.*` spans; `window` is the `bench.window` span.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

_HLO = re.compile(r"%(\S+) = .*?\s([a-z][a-z0-9-]*)\(")


def short_name(name: str) -> str:
    """'%fusion.7 = f32[..] fusion(...)' -> 'fusion.7 fusion'; other names
    as they are, cut to 120 characters."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def load(trace_dir) -> dict:
    import jax
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"device": {}, "host": [], "window": None}
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:TPU:"):
            out["device"][plane.name] = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ln in lines if ln.name == "XLA Ops"
                for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        span = [ev.name, float(ev.start_ns),
                                float(ev.duration_ns)]
                        out["host"].append(span)
                        if ev.name == "bench.window":
                            out["window"] = [span[1], span[1] + span[2]]
    if out["window"] is None:
        raise ValueError("the trace has no bench.window span")
    return out


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _clip(ev, w0, w1):
    s, e = max(ev[1], w0), min(ev[1] + ev[2], w1)
    return (s, e) if e > s else None


def reduce_busy(tr: dict) -> dict:
    """Busy seconds (union of op intervals in the window, averaged over
    the chips) and the window's length."""
    w0, w1 = tr["window"]
    per = [_union(filter(None, (_clip(ev, w0, w1) for ev in evs)))
           for evs in tr["device"].values()]
    busy_ns = sum(per) / len(per) if per else 0.0
    return {"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9}


def _gaps(tr: dict) -> list:
    """(start, end) of the first chip's idle stretches in the window,
    those at the window's edges included."""
    w0, w1 = tr["window"]
    first = next(iter(tr["device"].values()), [])
    ivs = sorted(filter(None, (_clip(ev, w0, w1) for ev in first)))
    gaps, end = [], w0
    for s, e in ivs:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if w1 > end:
        gaps.append((end, w1))
    return gaps


def boundary_idle_pct(tr: dict, period_s: float) -> float | None:
    """Idle share of a period whose boundary the window spans: the
    window's longest idle stretch is the boundary's gap, which has to lie
    inside it, with busy time on both sides; the window's other idle time
    stands for the rest of the period at its own rate."""
    if not period_s or period_s <= 0:
        return None
    w0, w1 = tr["window"]
    gaps = _gaps(tr)
    if not gaps:
        return None
    g0, g1 = max(gaps, key=lambda g: g[1] - g[0])
    if g0 <= w0 or g1 >= w1:
        return None
    gap_s = (g1 - g0) / 1e9
    b = reduce_busy(tr)
    rest_s = b["window_s"] - gap_s
    other = max(0.0, b["window_s"] - b["busy_s"] - gap_s)
    rate = other / rest_s if rest_s > 0 else 0.0
    return 100.0 * (gap_s + rate * max(0.0, period_s - gap_s)) / period_s


def kernel_s(tr: dict, needle: str) -> float | None:
    """Seconds of the ops whose name holds `needle`, summed over the
    window and averaged over the chips; None where there are none."""
    w0, w1 = tr["window"]
    per, found = [], False
    for evs in tr["device"].values():
        t = 0.0
        for ev in evs:
            if needle in ev[0]:
                c = _clip(ev, w0, w1)
                if c:
                    t += c[1] - c[0]
                    found = True
        per.append(t)
    return sum(per) / len(per) / 1e9 if found else None


def scan_steps(tr: dict) -> int | None:
    """Iterations of the scan in the window: every op of a scan body runs
    once an iteration, so the median count of the ops that repeat is the
    iteration count (give or take the one cut by the window's edge)."""
    w0, w1 = tr["window"]
    first = next(iter(tr["device"].values()), [])
    counts = {}
    for ev in first:
        if _clip(ev, w0, w1):
            counts[ev[0]] = counts.get(ev[0], 0) + 1
    reps = sorted(c for c in counts.values() if c > 1)
    return reps[len(reps) // 2] if reps else None


def breakdown(tr: dict, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps with
    the host span that was open at each gap's middle."""
    w0, w1 = tr["window"]
    tot = {}
    first = next(iter(tr["device"].values()), [])
    for ev in first:
        c = _clip(ev, w0, w1)
        if c:
            tot[ev[0]] = tot.get(ev[0], 0.0) + (c[1] - c[0])
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = _gaps(tr)
    spans = sorted(tr["host"], key=lambda h: h[2])
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        host = next((h[0] for h in spans
                     if h[0] != "bench.window" and h[1] <= mid <= h[1] + h[2]),
                    "harness")
        named.append([host, (e - s) / 1e9])
    return {"device_ops": [[short_name(n), t / 1e9] for n, t in ops],
            "idle_gaps": named}
