import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count on first init). Do not move them.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (SPMD partitioning succeeds),
  * the program fits (memory_analysis),
  * and it yields the roofline inputs (cost_analysis FLOPs/bytes +
    collective bytes parsed from the partitioned HLO).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--resume]      # subprocess per cell
  python -m repro.launch.dryrun --list
Results land in dryrun_results/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import math
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import dryrun_cells, get_config, get_shape
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_params, input_specs,
                                model_options_for, shardings_for)
from repro.models.model import decode_step, prefill
from repro.runtime.mesh_rules import use_mesh
from repro.runtime.train_loop import TrainConfig, make_train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "dryrun_results"
# the chip the pod meshes stand for; benchmarks/roofline.py keys its peak
# table by this (the compile itself runs on forced host devices)
TARGET_DEVICE_KIND = "TPU v5 lite"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16, "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s+(.*?)\s+(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter)(-start)?\(")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-chip collective byte totals by kind from partitioned HLO.

    Shapes in post-SPMD HLO are per-partition, so sums here are per-chip.
    Wire bytes use ring estimates: all-reduce 2x operand, all-gather 1x
    result, reduce-scatter 1x operand, all-to-all/permute 1x operand.
    """
    stats = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        result_bytes = _type_bytes(m.group(1))
        operand_bytes = _type_bytes(line[m.end():])
        s = stats.setdefault(kind, {"count": 0, "result_bytes": 0,
                                    "operand_bytes": 0})
        s["count"] += 1
        s["result_bytes"] += result_bytes
        s["operand_bytes"] += operand_bytes
    wire = 0
    for kind, s in stats.items():
        if kind == "all-reduce":
            wire += 2 * s["operand_bytes"]
        elif kind == "all-gather":
            wire += s["result_bytes"]
        else:
            wire += s["operand_bytes"]
    return {"by_kind": stats, "wire_bytes_per_chip": wire}


def model_param_counts(cfg) -> dict:
    """Exact param counts from abstract init; active scales MoE ffn by k/E."""
    shapes, _ = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = active = nonembed = 0
    moe_scale = (cfg.experts_per_token / cfg.num_experts) if cfg.is_moe else 1.0
    for path, leaf in flat:
        keys = "/".join(str(getattr(k, "key", k)) for k in path)
        n = leaf.size
        total += n
        if "embed/" in keys and "unembed" not in keys:
            continue
        nonembed += n
        if cfg.is_moe and "/ffn/" in keys and "router" not in keys:
            active += int(n * moe_scale)
        else:
            active += n
    return {"total": int(total), "nonembed": int(nonembed),
            "active_nonembed": int(active)}


def build_step(cfg, shape, opt, multi_pod: bool, dp_compress: str = "none"):
    if shape.kind == "train":
        tcfg = TrainConfig(num_pods=2 if multi_pod else 1,
                           dp_compress=dp_compress)
        return make_train_step(cfg, opt, tcfg), (0, 1)
    if shape.kind == "prefill":
        # VLM archs prepend `frontend_tokens` patch embeddings to the text
        max_len = shape.seq_len + cfg.frontend_tokens
        return (lambda p, b: prefill(p, cfg, b, max_len, opt)), ()
    return (lambda p, s, t, pos: decode_step(p, cfg, s, t, pos, opt)), (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_overrides=None, dump_hlo: bool = False,
             dp_compress: str = "none") -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "status": "started",
           "device_kind": TARGET_DEVICE_KIND,
           "opt_overrides": opt_overrides or {},
           "dp_compress": dp_compress}
    ok, reason = cfg.shape_supported(shape)
    if not ok:
        rec.update(status="skipped", skip_reason=reason)
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    opt = model_options_for(cfg, shape, **(opt_overrides or {}))
    args, axes = input_specs(cfg, shape, opt)
    in_sh = shardings_for(args, axes, mesh)
    step_fn, donate = build_step(cfg, shape, opt, multi_pod, dp_compress)
    with use_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        mem_rec[attr] = int(getattr(mem, attr, 0) or 0)
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)          # naive (loop bodies counted once)
    loop_aware = hlo_analyze(hlo)          # trip-count corrected (the truth)
    if dump_hlo:
        (OUT_DIR / f"{arch}__{shape_name}__{mesh_name}.hlo").write_text(hlo)
    counts = model_param_counts(cfg)
    factor = 6.0 if shape.kind == "train" else 2.0
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill")
              else shape.global_batch)
    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops=float(cost.get("flops", -1.0)),
        bytes_accessed=float(cost.get("bytes accessed", -1.0)),
        cost_analysis={k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))},
        memory_analysis=mem_rec,
        collectives=coll,
        loop_aware=loop_aware,
        params=counts,
        model_flops=factor * counts["active_nonembed"] * tokens,
        tokens=tokens,
        hlo_bytes=len(hlo),
    )
    return rec


def cell_list():
    cells = []
    for c in dryrun_cells():
        for multi in (False, True):
            cells.append({**c, "multi_pod": multi})
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma k=v ModelOptions overrides (hillclimb)")
    ap.add_argument("--dp-compress", default="none",
                    help="'int8': DaeMon-compressed pod-axis gradient sync")
    ap.add_argument("--tag", default="", help="suffix for result filename")
    args = ap.parse_args()
    OUT_DIR.mkdir(exist_ok=True)

    if args.list:
        for c in cell_list():
            print(c)
        return

    if args.all:
        failures = 0
        for c in cell_list():
            mesh_name = "multipod_2x16x16" if c["multi_pod"] else "pod_16x16"
            out = OUT_DIR / f"{c['arch']}__{c['shape']}__{mesh_name}.json"
            if args.resume and out.exists():
                st = json.loads(out.read_text()).get("status")
                if st in ("ok", "skipped"):
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", c["arch"], "--shape", c["shape"]]
            if c["multi_pod"]:
                cmd.append("--multi-pod")
            print(f"[dryrun-all] {c['arch']} {c['shape']} {mesh_name}",
                  flush=True)
            r = subprocess.run(cmd, cwd=str(OUT_DIR.parent))
            failures += int(r.returncode != 0)
        print(f"[dryrun-all] done, {failures} failures", flush=True)
        sys.exit(1 if failures else 0)

    overrides = {}
    for kv in filter(None, args.opt.split(",")):
        k, v = kv.split("=")
        overrides[k] = (v if not v.replace("-", "").isdigit() else int(v))
        if v in ("True", "False"):
            overrides[k] = v == "True"
    mesh_name = "multipod_2x16x16" if args.multi_pod else "pod_16x16"
    tag = f"__{args.tag}" if args.tag else ""
    out = OUT_DIR / f"{args.arch}__{args.shape}__{mesh_name}{tag}.json"
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod,
                       opt_overrides=overrides, dump_hlo=args.dump_hlo,
                       dp_compress=args.dp_compress)
    except Exception as e:  # record failures as first-class results
        rec = {"arch": args.arch, "shape": args.shape, "mesh": mesh_name,
               "status": "error", "error": repr(e),
               "traceback": traceback.format_exc()}
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: rec[k] for k in rec
                      if k not in ("traceback", "cost_analysis")},
                     indent=2))
    sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
