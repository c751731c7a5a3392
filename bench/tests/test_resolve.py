"""Every cell's configuration, traffic mix, entry and metric resolves by
name, and BENCHMARK.json keeps to its own schema."""
import json
import re

import pytest

import harness
from benchtest_support import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_by_name(workload):
    r = harness.resolve(workload, SPEC)
    assert hasattr(r["entry"], "Cell")
    assert r["mix"]["kind"]
    assert {m["name"] for m in r["e2e"]} >= {"setup_s"}
    assert len(r["e2e"]) >= 2 and r["per_layer"]
    for m in r["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     f"t_{m['name']}")
        assert callable(reader.read)


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_entry_declares_its_limits_and_control(workload):
    entry = harness.resolve(workload, SPEC)["entry"]
    assert entry.LIMITS and all(v >= 0 for v in entry.LIMITS.values())
    assert entry.CONTROL is not None
