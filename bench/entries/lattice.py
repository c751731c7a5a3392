"""Entry: the simulator's scheme x net lattice (`desim.simulate_lattice`).

One unit of work is one compiled lattice over one LLC-miss trace: every
scheme of the mix on every network point, `requests` misses each, ending
in `block_until_ready`. The window cycles over the traces made from the
seed in set-up. The check replays one lattice cell of every scheme (its
net and lattice run drawn from the seed) through the plain reference
(`bench/reference/desim_ref.py`) and compares every metric.
"""
from __future__ import annotations

import multiprocessing
import time
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

import exported
from lowered import bf16_round
from reference import desim_ref
import traffic

# Largest relative gap, over the checked cells' metrics, between the
# program and the float32 reference; set from the readings in PERF.md.
LIMITS = {"sim_metric_gap": 1e-5}
# the control: the reference with its simulated times in bfloat16
CONTROL = bf16_round
ROOT = Path(__file__).resolve().parents[2]
METRIC_KEYS = ("total_time_ns", "avg_miss_ns", "avg_access_ns", "hit_ratio",
               "pages_moved", "lines_moved", "net_bytes", "page_drops")


def _gap(got: dict, ref: dict) -> float:
    return max(abs(got[k] - ref[k]) / max(abs(ref[k]), 1e-30)
               for k in METRIC_KEYS)


class Cell:
    def __init__(self, cfg, mix, seed, devs, scale):
        self.cfg, self.seed, self.devs = cfg, seed, devs
        self.mix = dict(mix, **scale.get("mix", {}))
        self.requests = scale.get("requests", mix["requests"])
        self.schemes = list(self.mix["schemes"])
        grid = cfg["network"]["grid"]
        self.grid = [tuple(g) for g in grid][:scale.get("nets", len(grid))]
        self.results = []          # (trace index, device metrics dict)
        self._failed = 0

    # ------------------------------------------------------------ set-up
    def setup(self):
        import jax
        from repro.core.params import DaemonParams, NetworkParams
        from repro.sim import desim
        from repro.sim.schemes import SchemeFlags

        t0 = time.perf_counter()
        wl = self.cfg["workloads"][self.mix["workload"]]
        self.comp_ratio = wl["comp_ratio"]
        self.traces = [
            traffic.llc_miss_trace(wl, self.mix["workload"], self.requests,
                                   self.seed * 16 + k)
            for k in range(self.mix["traces"])]
        sim, dp, nw = self.cfg["sim"], self.cfg["daemon"], self.cfg["network"]
        scfg = desim.SimConfig(
            daemon=DaemonParams(**dp), local_frac=sim["local_frac"],
            num_mc=sim["num_mc"], mlp=sim["mlp"],
            placement=sim["placement"], num_cu=sim["num_cu"])
        flags = [SchemeFlags(name=n, **self.cfg["schemes"][n])
                 for n in self.schemes]
        nets = [desim.make_net(NetworkParams(
            dram_bw_gbps=nw["dram_bw_gbps"], bw_factor=bf,
            switch_latency_ns=sw,
            local_mem_latency_ns=nw["local_mem_latency_ns"],
            remote_mem_latency_ns=nw["remote_mem_latency_ns"],
            translation_latency_ns=nw["translation_latency_ns"]))
            for sw, bf in self.grid]
        args = []
        for tr in self.traces:
            (tflags, warm_after, arrays, stacked, cr, cus, pols, telcfg,
             *_) = desim._lattice_inputs(
                flags, scfg, tr, nets, self.comp_ratio, sim["warm_frac"],
                None, None, None)
            args.append(jax.device_put((tflags, warm_after, arrays,
                                        stacked, cr, cus, pols),
                                       self.devs[0]))
        self.args = args
        t1 = time.perf_counter()
        n_pages = self.traces[0].n_pages
        if self.devs[0].platform == "tpu":
            # on the chip the lattice's lowering (~20 s of tracing four
            # nested vmaps) is kept in the checkout across runs
            lowered, hit = exported.lower(
                "lattice", lambda *a: desim._lattice_jit(scfg, n_pages,
                                                         telcfg, *a),
                args[0], repr((scfg, n_pages, telcfg)),
                ROOT / ".bench_out" / "export", ROOT / "src" / "repro")
            t2 = time.perf_counter()
            flat = lowered.compile()
            self.compiled = lambda *a: flat(*jax.tree.leaves(a))
        else:
            lowered, hit = desim._lattice_jit.lower(
                scfg, n_pages, telcfg, *args[0]), False
            t2 = time.perf_counter()
            self.compiled = lowered.compile()
        self.phases = {"inputs_s": t1 - t0, "lower_s": t2 - t1,
                       "export_hit": hit,
                       "compile_s": time.perf_counter() - t2}
        self.n_units = 0

    # ------------------------------------------------------------ window
    def unit(self):
        import jax
        from jax import profiler
        k = self.n_units % len(self.args)
        with profiler.TraceAnnotation("bench.lattice.dispatch"):
            res = self.compiled(*self.args[k])
        with profiler.TraceAnnotation("bench.lattice.wait"):
            jax.block_until_ready(res)
        self.results.append((k, res))
        self.n_units += 1

    def traced_window(self, start_trace, stop_trace) -> int:
        """The traced window: the boundary between two lattices, from
        `trace_before_s` before the one ends to `trace_after_s` after the
        next is dispatched. A whole lattice is ~0.6 M device ops a second
        for ~16 s, more than a trace can hold; the lattice before it
        times the period, so that the trace starts while the device is
        still busy. Every lattice is checked like any other."""
        import jax
        from jax import profiler
        t0 = time.perf_counter()
        self.unit()
        t1 = time.perf_counter()
        lead = t1 - t0 - self.mix["trace_before_s"]
        k = self.n_units % len(self.args)
        res = self.compiled(*self.args[k])
        time.sleep(max(0.0, lead - (time.perf_counter() - t1)))
        start_trace()
        with profiler.TraceAnnotation("bench.window"):
            with profiler.TraceAnnotation("bench.lattice.wait"):
                jax.block_until_ready(res)
            self.results.append((k, res))
            self.n_units += 1
            k = self.n_units % len(self.args)
            t2 = time.perf_counter()
            with profiler.TraceAnnotation("bench.lattice.dispatch"):
                nxt = self.compiled(*self.args[k])
            self.period_s = t2 - t1
            with profiler.TraceAnnotation("bench.lattice.run"):
                time.sleep(self.mix["trace_after_s"])
        stop_trace()
        jax.block_until_ready(nxt)
        self.results.append((k, nxt))
        self.n_units += 1
        return self.n_units

    def cells(self) -> int:
        return len(self.schemes) * len(self.grid)

    def end_to_end(self, units, window_s):
        return {"sim_requests_per_s":
                units * self.requests * self.cells() / window_s}

    def attempted(self) -> int:
        return len(self.results) * self.cells()

    def failed(self) -> int:
        return self._failed

    def layer_context(self, units):
        return {"units": units, "cells": self.cells(),
                "period_s": getattr(self, "period_s", None)}

    # ------------------------------------------------------------- check
    def sample(self):
        """(lattice run, scheme, net) picks: every scheme once, its net
        and the run of the window it is read from drawn from the seed."""
        rng = np.random.default_rng([self.seed, 1])
        return [(int(rng.integers(0, len(self.results))), i,
                 int(rng.integers(0, len(self.grid))))
                for i in range(len(self.schemes))]

    def check(self, control=None):
        """The compared number with its limit. `control` = a rounding
        function puts the reference, computed in that precision, in the
        program's place: the run that has to fail. The references run in
        `check_workers` processes of their own, which touch no device."""
        import jax
        picks = self.sample()
        jobs = [(self.cfg, self.cfg["schemes"][self.schemes[i]],
                 self.grid[j], self.traces[self.results[u][0]],
                 self.comp_ratio, desim_ref.F32) for u, i, j in picks]
        if control is not None:
            jobs += [j[:-1] + (control,) for j in jobs]
        workers = min(self.mix.get("check_workers", 1), len(jobs))
        if workers > 1:
            ctx = multiprocessing.get_context("spawn")
            with ProcessPoolExecutor(workers, mp_context=ctx) as pool:
                refs = list(pool.map(desim_ref.simulate, *zip(*jobs)))
        else:
            refs = [desim_ref.simulate(*j) for j in jobs]
        gaps = []
        for n, (u, i, j) in enumerate(picks):
            res = self.results[u][1]
            got = ({key: float(jax.device_get(res[key])[i, j, 0, 0])
                    for key in METRIC_KEYS} if control is None
                   else refs[len(picks) + n])
            gaps.append(_gap(got, refs[n]))
        if control is None:
            self._failed = sum(g > LIMITS["sim_metric_gap"] for g in gaps)
        return [{"name": "sim_metric_gap", "value": max(gaps),
                 "limit": LIMITS["sim_metric_gap"]}]

