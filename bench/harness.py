"""Benchmark harness: finds a cell's files by name, runs it, prints its line.

`BENCHMARK.json` names each cell's configuration and traffic mix; the
configuration file names its entry driver (`bench/entries/<entry>.py`),
and each per-layer metric has a reader `bench/metrics/<name>.py`. Adding a
cell, mix, configuration, entry or metric adds files; it edits none.

One run: set-up (inputs and weights from the seed, compiled programs from
the cache, warm-up of the cell's own shapes), then a measured window of
`--seconds` (with `--trace 1`, a short window under the profiler
instead: `trace_units` units, or the cell's own `traced_window`), then
the peak device memory, then the check against the plain reference, and
last one JSON line on stdout.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(path: Path, name: str):
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def resolve(workload: str, bench_spec: dict = None) -> dict:
    """Every file a cell needs, found by the names in BENCHMARK.json."""
    bs = bench_spec or spec()
    cells = {w["name"]: w for w in bs["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bs["configs"]}[cell["config"]]
    cfg = load_json(ROOT / conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    entry = load_module(BENCH / "entries" / f"{cfg['entry']}.py",
                        f"bench_entry_{cfg['entry']}")
    e2e = [m for m in bs["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bs["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"cell": cell, "cfg": cfg, "mix": mix, "entry": entry,
            "e2e": e2e, "per_layer": layer}


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees {devs[0].platform!r} devices")
    if require_tpu and len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, scale: dict = None,
        log=None) -> dict:
    """One run of one cell; returns the result object (the last line).
    `require_tpu=False` and `scale` (smaller sizes) serve the tests."""
    import jax
    from jax import profiler

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    r = resolve(workload)
    devs = devices(r["cell"]["chips"], require_tpu)
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        # every program of the cell goes to the cache, however quick to
        # compile, so that only the first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"compile cache {cache}")
    cell = r["entry"].Cell(r["cfg"], r["mix"], seed, devs, scale or {})
    cell.setup()
    # set-up's objects leave the collector's view, so that no full
    # collection over them pauses a step of the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s!r} {getattr(cell, 'phases', {})}")

    trace_dir = OUT / f"trace-{workload}"
    t0 = time.perf_counter()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0

        def start_trace():
            profiler.start_trace(str(trace_dir), profiler_options=opts)
        if hasattr(cell, "traced_window"):
            units = cell.traced_window(start_trace, profiler.stop_trace)
        else:
            start_trace()
            with profiler.TraceAnnotation("bench.window"):
                for _ in range(cell.trace_units):
                    cell.unit()
            profiler.stop_trace()
            units = cell.trace_units
    else:
        units = 0
        while time.perf_counter() - t0 < seconds:
            cell.unit()
            units += 1
    window_s = time.perf_counter() - t0
    gc.unfreeze()
    log(f"window_s={window_s!r} units={units}")
    mem = peak_bytes(devs)
    ctx = cell.layer_context(units) if trace else None

    checks = cell.check()
    if getattr(cell, "summary", None):
        log(f"program ledger {cell.summary}")
    correct = all(c["value"] <= c["limit"] for c in checks)
    metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": cell.attempted(),
           "failed": cell.failed()}
    if trace:
        from trace_reduce import breakdown, load, reduce_busy
        tr = load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = reduce_busy(tr)
        device["busy_s"] = busy["busy_s"]
        device["window_s"] = busy["window_s"]
        ctx["busy"] = busy
        ctx["device_kind"] = devs[0].device_kind
        for m in r["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(tr, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = breakdown(tr)
    else:
        got = cell.end_to_end(units, window_s)
        got["setup_s"] = setup_s
        for m in r["e2e"]:
            metrics[m["name"]] = {"value": got[m["name"]], "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in checks}
    for c in checks:
        log(f"compared {c['name']}={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return out
