"""Paths and test-run sizes shared by the benchmark's own tests. Importing
it puts the program and the bench modules on the path; nothing here
touches a TPU."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# cell sizes a test run holds: the same mixes and configurations, fewer
# tenants and layers, narrower pages, shorter traces
SCALES = {
    "sim-pr-fig8": {"requests": 1500, "nets": 2},
    "store-olmoe-window-b64": {"mix": {"tenants": 4, "stream_steps": 48,
                                       "trace_units": 4},
                               "model": {"num_key_value_heads": 1,
                                         "num_hidden_layers": 2}},
}
