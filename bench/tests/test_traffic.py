"""The traffic generator is deterministic in the seed, and its LLC-miss
traces equal the program's calibrated generator today."""
import json

import numpy as np
import pytest

import traffic
from benchtest_support import BENCH

SIM = json.loads((BENCH / "configs" / "daemon-paper-sim.json").read_text())
STORE_MIXES = ["window-b64"]


def _mix(name, **kw):
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    return dict(mix, **kw)


@pytest.mark.parametrize("wl", ["pr", "sp"])
def test_llc_miss_equals_program_generator(wl):
    from repro.sim.trace import generate_trace
    from repro.sim.workloads import WORKLOADS
    ours = traffic.llc_miss_trace(SIM["workloads"][wl], wl, 800, 2**31 + 9)
    theirs = generate_trace(WORKLOADS[wl], 800, 2**31 + 9)
    for a, b in zip(ours[:4], theirs[:4]):
        np.testing.assert_array_equal(a, b)
    assert ours.n_pages == theirs.n_pages


@pytest.mark.parametrize("name", STORE_MIXES)
def test_store_streams_deterministic_per_seed(name):
    mix = _mix(name, stream_steps=40, tenants=6)
    a = traffic.store_streams(mix, 256, 16, 2**31 + 3)
    b = traffic.store_streams(mix, 256, 16, 2**31 + 3)
    c = traffic.store_streams(mix, 256, 16, 7)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    pages = a[0]
    tenant = np.arange(6)[None, :, None]
    assert pages.shape == (40, 6, 4)
    assert np.all(pages // 256 == tenant)       # each in its own region


@pytest.mark.parametrize("layers", [1, 3])
def test_layers_are_stores_with_the_tenants_requests(layers):
    mix = _mix("window-b64", stream_steps=20, tenants=5)
    one = traffic.store_streams(mix, 256, 16, 2**31 + 8)
    many = traffic.store_streams(mix, 256, 16, 2**31 + 8, layers)
    assert many[0].shape == (20, 5 * layers, 4)
    store = np.arange(5 * layers)[None, :, None]
    assert np.all(many[0] // 256 == store)      # each in its own region
    for layer in range(layers):
        np.testing.assert_array_equal(many[0][:, layer::layers] % 256,
                                      one[0] % 256)
        np.testing.assert_array_equal(many[1][:, layer::layers], one[1])
        np.testing.assert_array_equal(many[2][:, layer::layers], one[2])


def test_decode_window_advances_and_appends():
    mix = _mix("window-b64", stream_steps=3, tenants=2)
    pages, offs, writes = traffic.store_streams(mix, 256, 16, 11)
    assert writes[..., 0].all() and not writes[..., 1:].any()
    assert np.all(offs[..., 1:] == 15)
    pos = pages[..., 0] % 256 * 16 + offs[..., 0]
    assert np.all((np.diff(pos, axis=0) == 1) | (pos[1:] < pos[:-1]))
