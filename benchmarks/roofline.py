"""Roofline table from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh): three terms in seconds from the compiled HLO
(loop-aware analysis), dominant bottleneck, MODEL_FLOPS/HLO_FLOPS
usefulness, and roofline fraction = ideal-compute-time / dominant-term.

Hardware peaks come from `PEAKS`, keyed by the `device_kind` JAX reports
and recorded in each cell; a device that is not in the table is an error,
never a default.
"""
from __future__ import annotations

import glob
import json
import os
from pathlib import Path

# Per-chip peaks keyed by `jax.devices()[0].device_kind`. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s inter-chip interconnect over 4 links = 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The `PEAKS` row for `device_kind`; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}"
                         ) from None


RESULTS = Path(__file__).resolve().parent.parent / "dryrun_results"


def load_cells(pattern="*.json", base_only=True):
    """base_only filters out hillclimb-tagged variants (arch__shape__mesh
    is exactly three segments; tags append a fourth)."""
    cells = []
    for f in sorted(glob.glob(str(RESULTS / pattern))):
        if base_only and Path(f).stem.count("__") != 2:
            continue
        try:
            cells.append(json.loads(Path(f).read_text()))
        except Exception:
            pass
    return cells


def terms(cell) -> dict:
    pk = peaks(cell.get("device_kind"))
    la = cell.get("loop_aware", {})
    flops = la.get("flops_per_chip", 0.0)
    hbm = la.get("hbm_bytes_per_chip", 0.0)
    wire = la.get("wire_bytes_per_chip", 0.0)
    t_c = flops / pk["bf16_flops"]
    t_m = hbm / pk["hbm_bw"]
    t_n = wire / pk["ici_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])
    model_t = (cell.get("model_flops", 0.0) / cell.get("chips", 1)
               / pk["bf16_flops"])
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "tag": cell.get("opt_overrides") or {},
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_n,
        "dominant": dom[0], "t_dominant_s": dom[1],
        "usefulness": (cell.get("model_flops", 0.0) / cell.get("chips", 1)
                       / flops) if flops else 0.0,
        "roofline_fraction": model_t / dom[1] if dom[1] else 0.0,
        "temp_gib": cell.get("memory_analysis", {}).get(
            "temp_size_in_bytes", 0) / 2**30,
        "fits_16g": cell.get("memory_analysis", {}).get(
            "temp_size_in_bytes", 0) / 2**30 < 16.0,
        "status": cell.get("status"),
    }


def table(mesh="pod_16x16", pattern=None):
    rows = []
    for cell in load_cells(pattern or "*.json"):
        if cell.get("status") == "skipped":
            rows.append({"arch": cell["arch"], "shape": cell["shape"],
                         "mesh": cell["mesh"], "status": "skipped",
                         "skip_reason": cell.get("skip_reason", "")})
            continue
        if cell.get("status") != "ok":
            rows.append({"arch": cell.get("arch"), "shape": cell.get("shape"),
                         "mesh": cell.get("mesh"), "status": cell.get(
                             "status"), "error": str(cell.get("error"))[:80]})
            continue
        if mesh and cell["mesh"] != mesh:
            continue
        rows.append(terms(cell))
    return rows


def main():
    print("# roofline table (single-pod 16x16) — terms in seconds/step")
    hdr = ("arch,shape,t_compute,t_memory,t_collective,dominant,"
           "usefulness,roofline_frac,temp_GiB,fits")
    print(hdr)
    for r in table("pod_16x16"):
        if r.get("status") == "skipped":
            print(f"{r['arch']},{r['shape']},skipped ({r['skip_reason'][:40]})")
        elif r.get("status") not in ("ok", None) and "t_compute_s" not in r:
            print(f"{r.get('arch')},{r.get('shape')},{r.get('status')}")
        else:
            print(f"{r['arch']},{r['shape']},{r['t_compute_s']:.3f},"
                  f"{r['t_memory_s']:.3f},{r['t_collective_s']:.3f},"
                  f"{r['dominant']},{r['usefulness']:.2f},"
                  f"{r['roofline_fraction']:.3f},{r['temp_gib']:.1f},"
                  f"{r['fits_16g']}")


if __name__ == "__main__":
    main()
