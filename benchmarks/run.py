"""Benchmark aggregator: one function per paper table/figure + roofline.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig8,...]
Emits CSV blocks per figure and the paper-claim validation summary, plus
`BENCH_serve.json` (machine-readable batched-store serving metrics:
tokens/s, wire bytes, hit ratio) when the `serve` sweep runs,
`BENCH_robust.json` (adaptive-vs-static repartitioning under time-varying
link profiles, sim + store planes) when the `robust` sweep runs, and
`BENCH_scale.json` (compute-plane scaling: desim total time and
replicated-store tokens/s vs C compute units x M modules) when the
`scale` sweep runs, and `BENCH_capacity.json` (local-memory capacity
sensitivity: local:remote ratio x replacement policy x scheme on both
planes, the residency plane's graceful-degradation axis) when the
`capacity` sweep runs. Trace length via REPRO_BENCH_R (default 60000).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _early_devices() -> int:
    """Pre-parse --devices from argv BEFORE anything imports jax: the
    forced-host-device flag only works if it's in XLA_FLAGS when the
    backend initializes (same pattern as tests/_distributed_checks.py)."""
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return None


_DEVICES = _early_devices()
if _DEVICES and _DEVICES > 1 \
        and "host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_DEVICES}").strip()

import numpy as np

from benchmarks import (capacity, figures, robustness, roofline, scaling,
                        serving)
from benchmarks.common import ORDER
from benchmarks.validate import assert_bench_schema, check
from repro.launch.compile_cache import enable_compile_cache

BENCH_SERVE_JSON = Path("BENCH_serve.json")
BENCH_ROBUST_JSON = Path("BENCH_robust.json")
BENCH_SCALE_JSON = Path("BENCH_scale.json")
BENCH_CAPACITY_JSON = Path("BENCH_capacity.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short traces (20k) for CI")
    ap.add_argument("--only", default="",
                    help="comma list: fig3,fig8,fig9,... roofline")
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "pallas", "ref", "chain"),
                    help="store hot-path impl for the serve sweep "
                         "(KVStoreConfig.kernel_impl)")
    ap.add_argument("--devices", type=int, default=None,
                    help="run the scale sweep's mesh section on N forced "
                         "host devices (sets XLA_FLAGS before jax init; "
                         "sharded-vs-vmap columns in BENCH_scale.json)")
    args = ap.parse_args()
    enable_compile_cache()
    r = 20000 if args.quick else None
    only = set(filter(None, args.only.split(",")))

    def want(name):
        return not only or name in only

    t0 = time.time()
    values = {}

    if want("fig3"):
        f3 = figures.fig3_motivation(r)
        values["remote_slowdown_vs_local"] = 1.0 / f3["agg"]["local"] \
            if f3["agg"]["local"] < 1 else f3["agg"]["local"]
    f8 = None
    if want("fig8"):
        f8 = figures.fig8_speedup(r)
        values["daemon_speedup_avg"] = f8["agg"]["daemon"]
        values["daemon_bw2"] = f8["by_bw"][2.0]
        values["daemon_bw4"] = f8["by_bw"][4.0]
        values["daemon_bw8"] = f8["by_bw"][8.0]
    if want("fig9"):
        f9 = figures.fig9_access_cost(r, grid=f8["grid"] if f8 else None)
        values["daemon_access_cost_avg"] = f9["agg"]["daemon"]
        values["lc_access_cost_avg"] = f9["agg"]["lc"]
        values["pq_access_cost_avg"] = f9["agg"]["pq"]
    if want("fig10"):
        f10 = figures.fig10_hit_ratio(r)
        values["remote_hit_ratio_avg"] = f10["avg"]["remote"]
        values["daemon_hit_delta_vs_remote"] = (f10["avg"]["remote"]
                                                - f10["avg"]["daemon"])
    if want("fig11"):
        f11 = figures.fig11_bw_ratio(r)
        values["ratio25_beats_50"] = f11["agg"][0.25] / max(
            f11["agg"][0.50], 1e-9)
    if want("fig12"):
        f12 = figures.fig12_compression(r)
        values["lz_vs_fpcbdi"] = f12["agg"]["lz"] / f12["agg"]["fpcbdi"]
        values["lz_vs_fve"] = f12["agg"]["lz"] / f12["agg"]["fve"]
    if want("fig13"):
        figures.fig13_disturbance(r)
    if want("fig15"):
        figures.fig15_multithreaded(r)
    if want("fig16"):
        figures.fig16_fifo(r)
    if want("fig17"):
        figures.fig17_multi_mc(r)
    f22 = None
    if want("fig22"):
        f22 = figures.fig22_compute_scaling(r, quick=args.quick)
        values["daemon_vs_remote_c8"] = f22["agg"][8]
    if want("fig18"):
        figures.fig18_multi_workload(r)
    if want("fig20"):
        figures.fig20_switch_latency(r)
    if want("fig21"):
        figures.fig21_bw_factor(r)
    if want("serve"):
        sv = serving.serve_sweep(quick=args.quick, impl=args.impl,
                                 trace_path="TRACE_serve.json")
        assert_bench_schema(BENCH_SERVE_JSON.name, sv)
        BENCH_SERVE_JSON.write_text(json.dumps(sv, indent=2) + "\n")
        print(f"# BENCH_serve.json written: "
              f"{sv['tokens_per_s']:.0f} tok/s, "
              f"{sv['wire_bytes']/1e6:.2f}MB wire, "
              f"hit {sv['hit_ratio']:.3f}, "
              f"fused_vs_ref_tokens_ratio "
              f"{sv['fused_vs_ref_tokens_ratio']:.3f}")
        print(f"# serve tail: stall p50 {sv['stall_p50_steps']:.2f} / "
              f"p99 {sv['stall_p99_steps']:.2f} steps "
              f"(trace: {sv['trace_file']})")
    if want("robust"):
        rb = robustness.robust_sweep(quick=args.quick)
        assert_bench_schema(BENCH_ROBUST_JSON.name, rb)
        BENCH_ROBUST_JSON.write_text(json.dumps(rb, indent=2) + "\n")
        hl = rb["headline"]
        values["daemon_tail_vs_mean"] = hl["tail_vs_mean"]
        print(f"# BENCH_robust.json written: adaptive-vs-best-static "
              f"desim {hl['desim_best_win']:.3f}x, "
              f"store {hl['store_best_win']:.3f}x")
        print(f"# robust tail: daemon p99 win {hl['tail_p99_win']:.2f}x "
              f">= mean win {hl['tail_mean_win']:.2f}x "
              f"(ratio {hl['tail_vs_mean']:.3f})")
    if want("scale"):
        sc = scaling.scale_sweep(quick=args.quick,
                                 desim=f22["desim"] if f22 else None,
                                 devices=args.devices)
        assert_bench_schema(BENCH_SCALE_JSON.name, sc)
        BENCH_SCALE_JSON.write_text(json.dumps(sc, indent=2) + "\n")
        hl = sc["headline"]
        print(f"# BENCH_scale.json written: store tokens/s C8-vs-C1 "
              f"daemon {hl['daemon_speedup_c_max']:.2f}x, remote "
              f"{hl['remote_speedup_c_max']:.2f}x "
              f"(gap {hl['scaling_gap']:.2f}x)")
    if want("capacity"):
        cp = capacity.capacity_sweep(quick=args.quick)
        assert_bench_schema(BENCH_CAPACITY_JSON.name, cp)
        BENCH_CAPACITY_JSON.write_text(json.dumps(cp, indent=2) + "\n")
        hl = cp["headline"]
        values["daemon_capacity_slope"] = hl["capacity_gap"]
        print(f"# BENCH_capacity.json written: 20%->5% slowdown daemon "
              f"{hl['daemon_slowdown_5pct']:.3f}x vs remote "
              f"{hl['remote_slowdown_5pct']:.3f}x "
              f"(gap {hl['capacity_gap']:.3f}x)")
    if want("roofline"):
        roofline.main()

    if values:
        check(values)
    print(f"# total wall time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
