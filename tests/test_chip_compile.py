"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test compiles for one chip of a described `v5e:2x2` topology — the
TPU compiler runs here without a chip attached — and checks that the
compiled program calls the kernel (`tpu_custom_call`). Interpret-mode
tests cannot catch what only Mosaic refuses (block shapes off the
(8, 128) tiling, scalar stores to VMEM, vector lanes read at a traced
index); these do. Nothing runs, so they say nothing about results.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test-runner worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import residency
from repro.kernels import paged_gather as PG
from repro.kernels import residency_fused as RF

BF16 = jnp.bfloat16
I32 = jnp.int32
F32 = jnp.float32

# benchmarks/serving.py's hot path: B=64 tenants, 256x16 pools,
# 16 in-flight pages, 4 requests per step, 8192 remote pages per tenant,
# (4, 1, 8) rows
HOT = dict(b=64, sets=256, ways=16, p=16, r=4, n_remote=64 * 8192,
           row=(4, 1, 8))
# qwen3-1.7b paged serving: B=4, a fully associative 16-page pool, the
# default 256-entry in-flight buffer, a 4-page request window, 32 remote
# pages per tenant, KV rows of 16 tokens x 8 heads x 128 dims
SERVE = dict(b=4, sets=1, ways=16, p=256, r=4, n_remote=4 * 32,
             row=(16, 8, 128))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _fused_args(sharding, b, sets, ways, p, r, n_remote, row):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                               sharding=sharding)
    tbl = (b, sets, ways)
    res = residency.ResidencyState(page=s(tbl, I32), age=s(tbl, F32),
                                   ready=s(tbl, F32),
                                   dirty=s(tbl, jnp.bool_),
                                   rrpv=s(tbl, F32))
    pool = s((b, sets * ways) + row, BF16)
    remote = s((n_remote,) + row, BF16)
    pol = residency.PolicyFlags(touch_refresh=s((), jnp.bool_),
                                dirty_penalty=s((), F32),
                                rrip=s((), jnp.bool_))
    return (res, pool, pool, remote, remote, s((b, p), jnp.bool_),
            s((b, p), I32), s((b, r), I32), s((b, r), jnp.bool_),
            s((), F32), pol)


@pytest.mark.parametrize("shape", [HOT, SERVE], ids=["hot_path", "serve"])
def test_fused_residency_kernel_compiles(one_chip, shape):
    args = _fused_args(one_chip, **shape)
    compiled = jax.jit(RF.fused_residency_step).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert RF.in_compiled(text)


@pytest.mark.parametrize("n_remote,n_idx,row", [
    (HOT["n_remote"], HOT["b"] * HOT["r"], HOT["row"]),
    (SERVE["n_remote"], SERVE["b"] * SERVE["r"], SERVE["row"]),
], ids=["hot_path", "serve"])
def test_paged_gather_compiles_at_remote_fetch_shape(one_chip, n_remote,
                                                     n_idx, row):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                               sharding=one_chip)
    compiled = jax.jit(PG.paged_gather).lower(
        s((n_remote,) + row, BF16), s((n_idx,), I32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
