"""Readings that set the limits of `correct`: sound runs and the control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in one process: the cell's set-up, a short window, then the
compared numbers of the program against the plain reference (the sound
reading), then the same numbers with the control in the program's place:
the reference computed one precision below the configuration's, as the
cell's entry declares it (`CONTROL`; its limits are `LIMITS`). One JSON
line per seed and side. The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(workload, seed, seconds, *, with_control=True,
             require_tpu=True, scale=None):
    import harness
    r = harness.resolve(workload)
    devs = harness.devices(r["cell"]["chips"], require_tpu)
    cell = r["entry"].Cell(r["cfg"], r["mix"], seed, devs, scale or {})
    cell.setup()
    t0 = time.perf_counter()
    units = 0
    while units < 1 or time.perf_counter() - t0 < seconds:
        cell.unit()
        units += 1
    ctl = cell.check(control=r["entry"].CONTROL) if with_control else []
    sound = cell.check()
    return ({c["name"]: c["value"] for c in sound},
            {c["name"]: c["value"] for c in ctl}, units)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        sound, ctl, units = readings(args.workload, seed, args.seconds,
                                     with_control=n < args.control_seeds)
        line = {"workload": args.workload, "seed": seed, "units": units,
                "sound": sound, "s": time.perf_counter() - t0}
        if n < args.control_seeds:
            line["control"] = ctl
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
